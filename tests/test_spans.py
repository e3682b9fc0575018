"""Engine and serving spans: recorded only under a running profiler trace,
nested by parent index, on the trace's clock after one offset, bounded."""

import glob
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as engine_lib
from repro import serving
from repro.core.ising import random_graph
from repro.engine import spans


def _patterns(seed: int, p: int, n: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice([-1, 1], (p, n)), jnp.int8)


def _corrupt(xi, row: int, flips: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = np.asarray(xi[row]).copy()
    idx = rng.choice(v.size, flips, replace=False)
    v[idx] = -v[idx]
    return v


def _retrieval(eng):
    xi = _patterns(1, 4, 64)
    eng.install("mem", "retrieval", xi=xi, max_cycles=20, settle_chunk=1)
    singles = [_corrupt(xi, i % 4, 6, 30 + i) for i in range(4)]
    return [engine_lib.Request("mem", s) for s in singles] + [
        engine_lib.Request("mem", np.stack(singles[:2]))]


def _maxcut(eng):
    eng.install("cuts", "maxcut", sweeps=4)
    root = jax.random.PRNGKey(3)
    return [
        engine_lib.Request("cuts", random_graph(jax.random.fold_in(root, i), n, 0.5),
                           key=jax.random.fold_in(root, 100 + i))
        for i, n in enumerate((20, 24, 20))
    ]


WORKLOADS = {"retrieval": _retrieval, "maxcut": _maxcut}


def _serve(workload):
    """Results (as numpy) of one fresh engine serving the workload's requests."""
    eng = engine_lib.Engine(jax.random.PRNGKey(0), batch_buckets=(1, 2, 4))
    futs = [eng.submit(r) for r in WORKLOADS[workload](eng)]
    eng.drain()
    return [jax.device_get(f.result()) for f in futs], eng


def _start(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _host_events(trace_dir, names):
    """{name: sorted xplane start ns} of the host plane's events in ``names``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.setdefault(e.name, []).append(e.start_ns)
    return {k: sorted(v) for k, v in out.items()}


@pytest.fixture(scope="module", params=sorted(WORKLOADS))
def traced(request, tmp_path_factory):
    """(workload, records, trace dir) of one drain under the profiler."""
    trace_dir = tmp_path_factory.mktemp(f"trace_{request.param}")
    _serve(request.param)  # compile outside the trace
    lo = time.perf_counter_ns()
    _start(trace_dir)
    try:
        _serve(request.param)
    finally:
        jax.profiler.stop_trace()
    return request.param, spans.records(lo, time.perf_counter_ns()), trace_dir


class _NoSpan:
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_untraced_records_nothing_and_results_match(workload, monkeypatch):
    lo = time.perf_counter_ns()
    untraced, eng = _serve(workload)
    assert spans.records(lo, time.perf_counter_ns()) == []
    # The planner still gets slab seconds with tracing off.
    observed = eng.planner.snapshot()["ema_seconds"].values()
    assert observed and all(v > 0 for v in observed)
    monkeypatch.setattr(spans, "span", lambda name, **attrs: _NoSpan())
    plain, _ = _serve(workload)
    for got, want in zip(untraced, plain):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


def test_traced_spans_nest_and_link(traced):
    _, recs, _ = traced
    by_index = {r.index: r for r in recs}

    def children(rec, name):
        return [r for r in recs if r.parent == rec.index and r.name == name]

    def inside(child, parent):
        assert parent.start_ns <= child.start_ns <= child.end_ns <= parent.end_ns

    (drain,) = [r for r in recs if r.name == spans.DRAIN]
    slabs = children(drain, spans.SLAB)
    assert slabs and len(children(drain, spans.STATS)) == 1
    # Each slab's result crosses to the host once, inside its split.
    assert len([r for r in recs if r.name == spans.SYNC]) == len(slabs)
    for slab in slabs:
        inside(slab, drain)
        assert slab.attrs["workload"] in ("mem", "cuts")
        assert slab.attrs["requests"] == len(slab.attrs["ids"])
        assert slab.attrs["lanes"] <= slab.attrs["width"]
        for name in (spans.PACK, spans.SOLVE, spans.SPLIT):
            (child,) = children(slab, name)
            inside(child, slab)
        (split,) = children(slab, spans.SPLIT)
        (sync,) = children(split, spans.SYNC)
        inside(sync, split)
    submits = [r for r in recs if r.name == spans.SUBMIT]
    served = sorted(i for s in slabs for i in s.attrs["ids"])
    assert sorted(s.attrs["request"] for s in submits) == served
    for sub in submits:
        for name in (spans.VALIDATE, spans.QUOTE):
            (child,) = children(sub, name)
            inside(child, sub)
            assert child.attrs["request"] == sub.attrs["request"]
    assert all(r.parent is None or r.parent in by_index for r in recs)


def test_traced_spans_sit_on_the_trace_clock(traced):
    _, recs, trace_dir = traced
    names = {r.name for r in recs}
    events = _host_events(trace_dir, names)
    assert set(events) == names
    pairs = []
    for name in names:
        mine = sorted(r.start_ns for r in recs if r.name == name)
        assert len(mine) == len(events[name])
        pairs += zip(mine, events[name])
    offset = statistics.median(x - r for r, x in pairs)
    assert max(abs(x - r - offset) for r, x in pairs) < 100_000


def test_buffer_bound_sets_dropped(tmp_path):
    rec = spans.Recorder(capacity=4)
    marks = []
    _start(tmp_path)
    try:
        for i in range(10):
            marks.append(time.perf_counter_ns())
            with rec.span("test.bound", i=i):
                pass
    finally:
        jax.profiler.stop_trace()
    assert rec.dropped == 6
    with pytest.raises(spans.SpansDropped):
        rec.records(marks[5], time.perf_counter_ns())  # span 5 was dropped
    kept = rec.records(marks[6], time.perf_counter_ns())
    assert [r.attrs["i"] for r in kept] == [6, 7, 8, 9]


def test_span_measures_with_tracing_off():
    rec = spans.Recorder()
    with rec.span("test.off") as s:
        time.sleep(0.002)
    assert s.seconds >= 0.002
    assert rec.records(0, time.perf_counter_ns()) == [] and rec.dropped == 0


def test_continuous_step_records_phases(tmp_path):
    xi = _patterns(2, 3, 32)
    eng = serving.ContinuousEngine(jax.random.PRNGKey(8), batch_buckets=(1, 2, 4), slab_lanes=4)
    eng.install("mem", "retrieval", xi=xi, max_cycles=20, settle_chunk=1)
    for i in range(2):
        eng.submit(engine_lib.Request("mem", _corrupt(xi, i, 4, 50 + i)))
    untraced = eng.step()  # compiles outside the trace
    assert untraced["slab_seconds"] and all(v > 0 for v in untraced["slab_seconds"].values())
    eng.submit(engine_lib.Request("mem", _corrupt(xi, 2, 4, 52)))
    lo = time.perf_counter_ns()
    _start(tmp_path)
    try:
        report = eng.step()
    finally:
        jax.profiler.stop_trace()
    recs = spans.records(lo, time.perf_counter_ns())
    (tick,) = [r for r in recs if r.name == spans.TICK]
    phase = {r.name: r for r in recs if r.parent == tick.index}
    assert set(phase) == {spans.BACKFILL, spans.ADVANCE, spans.HARVEST}
    assert phase[spans.HARVEST].attrs["slab"] in report["slab_seconds"]
    assert any(r.name == spans.SYNC and r.parent == phase[spans.HARVEST].index for r in recs)
    ((_, seconds),) = report["slab_seconds"].items()
    assert seconds == pytest.approx(sum(
        (phase[n].end_ns - phase[n].start_ns) / 1e9 for n in (spans.ADVANCE, spans.HARVEST)))
