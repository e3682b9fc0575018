"""Host milliseconds per request inside the benchmark's span around
``Engine.drain`` (closed-loop cells): dispatch, the solve, and the
per-request result slicing."""


def read(ctx):
    out = ctx.outcome
    if out.attempted == 0 or out.host["drain_s"] <= 0:
        return None
    return 1000.0 * out.host["drain_s"] / out.attempted
