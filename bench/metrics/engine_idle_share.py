"""Share of the traced window in which the device is idle while the host is
inside the engine's own work: the program's ``engine.validate``,
``engine.quote``, ``engine.pack`` or ``engine.split`` spans.  Averaged over
the chips used, as ``device_idle_share.batch`` is, which bounds it.

The spans' ``perf_counter_ns`` stamps go onto the trace clock by one offset,
the window span's start less the outcome's start (``t_start``, taken a few
microseconds after the window span opens on the same thread).  On a TPU v5e
that offset sat 14-24 us before the one matched from each span's own trace
event, which moved the share by under 0.003 percentage points.  None without
such spans, or where the span buffer dropped records of the window.
"""

from program_spans import window_records

PHASES = ("engine.validate", "engine.quote", "engine.pack", "engine.split")


def _overlap(a, b):
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.hi <= ctx.lo:
        return None
    recs = window_records(ctx)
    if recs is None:
        return None
    offset = ctx.lo - ctx.outcome.t_start * 1e9
    host = ctx.tr.union([(r.start_ns + offset, r.end_ns + offset)
                         for r in recs if r.name in PHASES], ctx.lo, ctx.hi)
    if not host:
        return None
    inside = ctx.tr.length(host)
    idle = [inside - _overlap(host, ctx.tr.union(d.ops, ctx.lo, ctx.hi))
            for d in ctx.devices_used()]
    return 100.0 * (sum(idle) / len(idle)) / (ctx.hi - ctx.lo)
