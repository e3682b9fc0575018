"""Reduction of a profiler trace to the numbers the per-layer readers use.

A traced run writes one ``.xplane.pb``.  ``load`` keeps, per device plane
(``/device:TPU:<i>``), the intervals of its ``XLA Ops`` line (what ran on
the chip), and from the host plane every span the benchmark opened
(``bench.*``).  Every function below works on those intervals only, in
nanoseconds of the trace's own clock, which the profiler shares between
host and device.

* ``busy_ns``: length of the union of a device's op intervals inside the
  window.
* ``kernel``: launches of a Pallas kernel (its custom call is named after
  the jitted wrapper that makes it, e.g. ``%_coupling_sum_jit.3 = ...``),
  their summed device time, and their operand and result shapes parsed from
  the op's HLO text.
* ``idle_gaps``: the device's idle intervals inside the window, each put to
  the host span that covers its midpoint (``host.other`` when none does),
  summed per span name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

_TYPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


class Device:
    def __init__(self, name):
        self.name = name
        self.ops = []  # (start_ns, end_ns, hlo text)


class Trace:
    def __init__(self):
        self.devices = []
        self.spans = []  # (start_ns, end_ns, name) of bench.* host spans

    def window(self, name="bench.window"):
        """(start, end) of the benchmark's window span."""
        hits = [(s, e) for s, e, n in self.spans if n == name]
        if not hits:
            raise ValueError(f"no {name} span in the trace")
        return hits[0]


def find_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return files[0]


def from_profile(pd, span_prefix="bench."):
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            tr.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        tr.spans.append((e.start_ns, e.end_ns, e.name))
    tr.devices.sort(key=lambda d: d.name)
    return tr


def load(trace_dir):
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(trace_dir)))


def union(intervals, lo, hi):
    """Sorted disjoint intervals covering ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def busy_ns(dev, lo, hi):
    return length(union(dev.ops, lo, hi))


def shapes(text):
    """(result types, operand types) of an HLO op text, each a list of
    (dtype, dims) — e.g. ``("s8", (32, 64, 1024))``."""
    name, _, rhs = text.partition(" = ")
    call = rhs.find("(", rhs.find("custom-call") if "custom-call" in rhs else 0)
    head, args = rhs[:call], rhs[call:]
    args = args[: args.find("), ") + 1] if "), " in args else args

    def parse(s):
        return [(d, tuple(int(x) for x in dims.split(",") if x)) for d, dims in _TYPE.findall(s)]

    return parse(head), parse(args)


def kernel(dev, pattern, lo, hi):
    """Launches of the custom call named ``pattern`` on ``dev`` that start
    inside [lo, hi]: (list of (duration_ns, results, operands))."""
    prefix = "%" + pattern
    out = []
    for s, e, text in dev.ops:
        if lo <= s < hi and text.startswith(prefix) and "custom-call" in text:
            res, args = shapes(text)
            out.append((e - s, res, args))
    return out


def idle_gaps(dev, spans, lo, hi):
    """{host span name: idle ns} for the device's idle time in [lo, hi]."""
    busy = union(dev.ops, lo, hi)
    gaps = []
    cur = lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    # The benchmark's spans other than the window follow one another on
    # one thread, so the one that covers a point is the last to start
    # before it.
    inner = sorted(sp for sp in spans if sp[2] != "bench.window")
    starts = [sp[0] for sp in inner]
    out = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        k = bisect.bisect_right(starts, mid) - 1
        name = inner[k][2] if k >= 0 and inner[k][1] >= mid else "host.other"
        out[name] += e - s
    return dict(out)


def top_ops(dev, lo, hi, count=10):
    """The ``count`` op names (HLO instruction names) with most device time."""
    tot = defaultdict(float)
    for s, e, text in dev.ops:
        if lo <= s < hi:
            tot[text.split(" = ")[0]] += e - s
    return sorted(tot.items(), key=lambda kv: -kv[1])[:count]
