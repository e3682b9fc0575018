"""Host milliseconds per request attempted in the traced window, in the
program's ``engine.solve`` spans: each slab's call into the jitted solve until
it returns, which is the host's dispatch of the solve.  JAX runs the solve
asynchronously, so its device time is not in this span; the device trace
holds it."""

from program_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "SOLVE")
