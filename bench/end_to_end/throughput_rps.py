"""Requests completed in the window over the time from the window's start
to the last completion (host clock).  What one request is, each cell's
``why`` says."""


def read(ctx):
    out = ctx.outcome
    span = out.t_last - out.t_start
    if not out.served or span <= 0:
        return None
    return len(out.served) / span
