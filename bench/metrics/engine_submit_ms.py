"""Host milliseconds per request inside the benchmark's span around
``Engine.submit`` (closed-loop cells)."""


def read(ctx):
    out = ctx.outcome
    if out.attempted == 0 or out.host["submit_s"] <= 0:
        return None
    return 1000.0 * out.host["submit_s"] / out.attempted
