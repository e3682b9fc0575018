"""Share of its roofline that the multi-cycle chunk kernel reaches: the
least time of its launches in the traced window (the larger of operations
over the int8 peak and bytes over HBM bandwidth, ``bench/kernels/
phase_step_multi.py``) over their device time from the trace."""


def read(ctx):
    return ctx.roofline("phase_step_multi")
