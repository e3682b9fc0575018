"""Host milliseconds per request attempted in the traced window, in the
program's ``engine.split`` spans: each slab's result cut into per-request
results."""

from program_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "SPLIT")
