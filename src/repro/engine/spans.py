"""Host spans on the profiler's clock, inside the engine and the serving tick.

``with span(name, **attrs) as s:`` always reads ``time.perf_counter_ns()``
at entry and exit, so ``s.seconds`` is there for the planner and the
straggler monitor whether or not anything is traced.  Only while a
``jax.profiler`` trace is running does a span do more: it opens a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so it shows
in the trace's host plane beside the device ops, and it appends a
:class:`Record` to a bounded in-memory buffer that a benchmark reads after
its window.  Records stamped with ``perf_counter_ns`` sit on the trace's
clock after one constant offset.

Names are the constants below, so the program, the benchmark's readers and
the docs spell them one way.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

SUBMIT = "engine.submit"
VALIDATE = "engine.validate"  # lane_count, signature, bucket
QUOTE = "engine.quote"  # the planner's estimate with its cost units and FPGA quotes
DRAIN = "engine.drain"
SLAB = "engine.slab"  # one solve_bucket call; a sharded one adds the adapter's slab_attrs
PACK = "engine.pack"  # payloads and keys into one padded slab
SOLVE = "engine.solve"  # the jitted solve's call: its dispatch, not its device time
SPLIT = "engine.split"  # the slab's result cut into per-request results
STATS = "engine.stats"
SYNC = "engine.sync"  # a blocking device-to-host read
TICK = "serving.tick"
BACKFILL = "serving.backfill"
ADVANCE = "serving.advance"
HARVEST = "serving.harvest"

#: Records kept; older ones are dropped and counted.
CAPACITY = 65536


class Record(NamedTuple):
    index: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]  # index of the enclosing span on the same thread
    attrs: Dict[str, Any]


class SpansDropped(RuntimeError):
    """Records that started inside the asked window were dropped."""


class Span:
    """One span: ``seconds`` once it has closed; recorded only under a trace."""

    __slots__ = ("_rec", "name", "attrs", "start_ns", "end_ns", "_ann", "_index", "_parent")

    def __init__(self, recorder: "Recorder", name: str, attrs: Dict[str, Any]):
        self._rec, self.name, self.attrs = recorder, name, attrs
        self._ann = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
            self._index, self._parent = self._rec._push()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._rec._pop(Record(self._index, self.name, self.start_ns, self.end_ns,
                                  self._parent, self.attrs))


class Recorder:
    """The bounded buffer of traced spans, with a stack of open spans per thread."""

    def __init__(self, capacity: int = CAPACITY):
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count()
        self._local = threading.local()
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_start_ns = -1  # latest start among dropped records

    def span(self, name: str, **attrs: Any) -> Span:
        return Span(self, name, attrs)

    def _push(self):
        stack = self._local.__dict__.setdefault("stack", [])
        index = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(index)
        return index, parent

    def _pop(self, record: Record) -> None:
        self._local.stack.pop()
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
                self._dropped_start_ns = max(self._dropped_start_ns, self._buf[0].start_ns)
            self._buf.append(record)

    def records(self, lo_ns: float, hi_ns: float) -> List[Record]:
        """Records that start inside [lo_ns, hi_ns], by start; raises
        :class:`SpansDropped` if any such record was dropped."""
        with self._lock:
            if self._dropped_start_ns >= lo_ns:
                raise SpansDropped(
                    f"{self.dropped} span records dropped, the latest starting at "
                    f"{self._dropped_start_ns} ns, inside the window from {lo_ns} ns")
            out = [r for r in self._buf if lo_ns <= r.start_ns <= hi_ns]
        return sorted(out, key=lambda r: r.start_ns)


RECORDER = Recorder()
span = RECORDER.span
records = RECORDER.records
