"""Host milliseconds per request attempted in the traced window, in the
program's ``engine.pack`` spans: each slab built from its requests' payloads
and keys (conversions, padding, concatenation or stacking)."""

from program_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "PACK")
