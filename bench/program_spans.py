"""The program's own spans (``repro.engine.spans``) as the readers see them.

A traced run records each span with ``time.perf_counter_ns()`` stamps, the
clock of the outcome's ``t_start``, so a window's records are those that
start between ``t_start`` and ``t_start`` plus the window's length.  A
program without the module, or a buffer that dropped records of the window,
gives None: the readers then report nothing.
"""


def window_records(ctx):
    """Span records that start inside the traced window, by start; None for
    a program without spans or where records of the window were dropped."""
    try:
        from repro.engine import spans
    except ImportError:  # a program without spans
        return None
    t0 = ctx.outcome.t_start * 1e9
    try:
        return spans.records(t0, t0 + ctx.window_s * 1e9)
    except spans.SpansDropped:
        return None


def per_request_ms(ctx, constant):
    """Milliseconds per request attempted in the window, summed over the
    spans named by ``repro.engine.spans.<constant>``."""
    out = ctx.outcome
    if out.attempted == 0 or ctx.window_s <= 0:
        return None
    recs = window_records(ctx)
    if recs is None:
        return None
    from repro.engine import spans

    name = getattr(spans, constant)
    ns = [r.end_ns - r.start_ns for r in recs if r.name == name]
    return sum(ns) / 1e6 / out.attempted if ns else None
