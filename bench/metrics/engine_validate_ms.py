"""Host milliseconds per request attempted in the traced window, in the
program's ``engine.validate`` spans: ``lane_count``, ``signature`` and
``bucket`` of each submitted request, where its payload is converted."""

from program_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "VALIDATE")
