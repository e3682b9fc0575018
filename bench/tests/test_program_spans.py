"""The readers of the program's engine spans, on a hand-made context."""

import sys

import pytest

import run
import xplane as tr
from repro.engine import spans

NEW = ["engine_validate_ms", "engine_quote_ms", "engine_pack_ms", "engine_solve_ms",
       "engine_split_ms", "engine_idle_share"]
T0 = 5.0  # the outcome's start, perf_counter seconds
US = 1000  # ns


def _rec(i, name, lo_us, hi_us):
    return spans.Record(i, name, int(T0 * 1e9) + lo_us * US, int(T0 * 1e9) + hi_us * US,
                        None, {})


# One wave of two requests in a 100 µs window: host phases on the perf
# clock, device ops on the trace clock (window [0, 100000] ns).
RECORDS = [
    _rec(0, spans.VALIDATE, 0, 10),
    _rec(1, spans.QUOTE, 10, 15),
    _rec(2, spans.PACK, 20, 40),
    _rec(3, spans.SOLVE, 40, 60),
    _rec(4, spans.SPLIT, 60, 90),
    _rec(5, spans.VALIDATE, 200, 300),  # after the window
]
OPS = [(30 * US, 50 * US, "%fusion.1 = ..."), (65 * US, 70 * US, "%fusion.2 = ...")]


class _Outcome:
    attempted = 2
    t_start = T0


class _Recorder:
    def __init__(self, recs, dropped=False):
        self.recs, self.dropped = recs, dropped

    def records(self, lo_ns, hi_ns):
        if self.dropped:
            raise spans.SpansDropped("dropped")
        return [r for r in self.recs if lo_ns <= r.start_ns <= hi_ns]


def _ctx():
    trace = tr.Trace()
    dev = tr.Device("/device:TPU:0")
    dev.ops = list(OPS)
    trace.devices = [dev]
    return run.Context(trace=trace, tr=tr, outcome=_Outcome(), chips=1, lo=0.0,
                       hi=100.0 * US, window_s=100e-6, notes={})


def _read(name, ctx):
    return run.reader("metrics", name).read(ctx)


@pytest.fixture
def recorded(monkeypatch):
    def use(recs, dropped=False):
        monkeypatch.setattr(spans, "records", _Recorder(recs, dropped).records)
    use(RECORDS)
    return use


@pytest.mark.parametrize("name,ms", [
    ("engine_validate_ms", 0.010 / 2), ("engine_quote_ms", 0.005 / 2),
    ("engine_pack_ms", 0.020 / 2), ("engine_solve_ms", 0.020 / 2),
    ("engine_split_ms", 0.030 / 2)])
def test_ms_per_request(recorded, name, ms):
    assert _read(name, _ctx()) == pytest.approx(ms)


def test_idle_share_by_hand(recorded):
    # Host inside validate/quote/pack/split: [0,15] + [20,40] + [60,90] µs = 65 µs;
    # of it the device is busy [30,40] and [65,70] µs: idle 50 µs of 100.
    ctx = _ctx()
    assert _read("engine_idle_share", ctx) == pytest.approx(50.0)
    assert ctx.idle_share() == pytest.approx(75.0)


def test_idle_share_is_on_the_trace_clock(recorded):
    # A window span 3 µs before the outcome's start moves every span 3 µs
    # later on the device's clock, against the same ops.
    ctx = _ctx()
    ctx.lo, ctx.hi = -3.0 * US, 97.0 * US
    # Host [-3,12] + [17,37] + [57,87]; busy [30,37] and [65,70]: 65 - 12 = 53.
    assert _read("engine_idle_share", ctx) == pytest.approx(53.0)


@pytest.mark.parametrize("name", NEW)
def test_none_without_spans_or_when_dropped(recorded, monkeypatch, name):
    other = spans.VALIDATE if name == "engine_solve_ms" else spans.SOLVE
    recorded([r for r in RECORDS if r.name == other])
    assert _read(name, _ctx()) is None
    recorded(RECORDS, dropped=True)
    assert _read(name, _ctx()) is None
    recorded(RECORDS)
    # A program without spans.
    monkeypatch.delattr(sys.modules["repro.engine"], "spans")
    monkeypatch.setitem(sys.modules, "repro.engine.spans", None)
    assert _read(name, _ctx()) is None


@pytest.mark.parametrize("name", NEW)
def test_new_entries_read_program_spans(name):
    (m,) = [m for m in run.load_json(run.ROOT, "BENCHMARK.json")["per_layer"]
            if m["name"] == name]
    assert m["source"] == "program_span" and m["moves"] == "throughput_rps"
    assert m["layer"] == "engine"
    assert m["workloads"] == ["onn506.batch", "maxcut_g1.batch"]
    assert callable(run.reader("metrics", name).read)
