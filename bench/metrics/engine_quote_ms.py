"""Host milliseconds per request attempted in the traced window, in the
program's ``engine.quote`` spans: the planner's estimate for each submitted
request, with its cost units and FPGA quotes."""

from program_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "QUOTE")
