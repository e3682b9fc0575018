"""The trace reduction against a small trace with known answers."""

import os

import pytest

import run
import xplane as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.pbtxt")


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        return tr.from_profile(ProfileData.from_text_proto(f.read()))


def test_reads_devices_and_spans(small):
    assert [d.name for d in small.devices] == ["/device:TPU:0"]
    assert len(small.devices[0].ops) == 5
    assert sorted(n for _, _, n in small.spans) == [
        "bench.drain", "bench.fetch", "bench.submit", "bench.window"]
    assert small.window() == (0.0, 100000.0)


def test_busy_union_clips_to_window(small):
    assert tr.busy_ns(small.devices[0], 0.0, 100000.0) == 26000.0
    assert tr.busy_ns(small.devices[0], 0.0, 200000.0) == 36000.0


def test_kernel_launches_and_shapes(small):
    launches = tr.kernel(small.devices[0], "_coupling_sum_jit", 0.0, 100000.0)
    assert launches == [(10000.0, [("s32", (2, 8, 64))],
                         [("s8", (2, 8, 1024)), ("s8", (2, 64, 1024))])]
    assert tr.kernel(small.devices[0], "_phase_step_multi_jit", 0.0, 100000.0) == []


def test_idle_gaps_by_host_span(small):
    gaps = tr.idle_gaps(small.devices[0], small.spans, 0.0, 100000.0)
    assert gaps == {"bench.submit": 10000.0, "bench.drain": 64000.0}


def test_top_ops(small):
    top = dict(tr.top_ops(small.devices[0], 0.0, 100000.0))
    assert top == {"%_coupling_sum_jit.3": 10000.0, "%all-reduce.2": 12000.0,
                   "%fusion.1": 5000.0, "%fusion.7": 1000.0}


def test_context_readers(small):
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    ctx = run.Context(trace=small, tr=tr, chips=1, peak=peak, cfg={"settle_chunk": 8},
                      lo=0.0, hi=100000.0, notes={})
    assert ctx.idle_share() == pytest.approx(74.0)
    assert ctx.busy_s() == pytest.approx(26e-6)
    # 2*2*8*64*1024 ops, 16384 + 131072 + 4096 bytes: memory-bound.
    least = (16384 + 131072 + 4096) / 819e9
    assert ctx.roofline("coupling_sum") == pytest.approx(100.0 * least / 10e-6)
    assert ctx.notes["coupling_sum_roofline_bound"] == "memory"
    assert ctx.roofline("phase_step_multi") is None
