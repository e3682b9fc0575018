"""Every entry of BENCHMARK.json resolves to its files by name, and the
file keeps to the benchmark's format."""

import json
import os
import re

import pytest

import run

SPEC = run.load_json(run.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _exists(*parts):
    return os.path.isfile(os.path.join(run.BENCH, *parts))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    spec, got, cfg, traffic = run.resolve(cell["name"])
    assert got is not None and cfg["name"] == cell["config"]
    assert _exists("systems", cfg["system"] + ".py")
    assert traffic["loop"] == "closed"
    assert cell["chips"] == cfg["chips"]
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    reported = [m for m in SPEC["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    layered = [m for m in SPEC["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layered


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    path = os.path.join(run.ROOT, cfg["file"])
    assert path.startswith(os.path.join(run.BENCH, "configs"))
    with open(path) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"] and body["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader(metric):
    kind = "metrics" if metric in SPEC["per_layer"] else "end_to_end"
    mod = run.reader(kind, metric["name"])
    assert callable(mod.read)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in SOURCES and metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if kind == "metrics":
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
            assert _exists("kernels", metric["name"][: -len("_roofline")] + ".py")
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


def test_benchmark_format():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    assert os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_peak_table_has_the_v5e():
    peaks = run.load_json(run.BENCH, "peaks.json")
    assert peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["int8_ops_per_s"] == 393e12
