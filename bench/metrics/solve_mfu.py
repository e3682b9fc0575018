"""Whole-solve share of the chip's int8 peak.

Int8 operations the algorithm needs for the requests completed in the
traced window (2·N² per lane per required cycle, from the returned results
and the reference's freeze cycles; for Max-Cut 2·N² per replica per sweep
run), over window seconds × chips × int8 peak.  Counts no padding and no
speculative cycles, so it reads the same work whatever implements it.
"""


def read(ctx):
    if ctx.useful_ops is None or ctx.window_s <= 0:
        return None
    share = 100.0 * ctx.useful_ops / (ctx.window_s * ctx.chips * ctx.peak["int8_ops_per_s"])
    return share if share > 0 else None
