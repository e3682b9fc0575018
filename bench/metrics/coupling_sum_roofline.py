"""Share of its roofline that the row-slab coupling kernel reaches (the
Ising field evaluations): least time by ``bench/kernels/coupling_sum.py``
over the launches' device time from the trace."""


def read(ctx):
    return ctx.roofline("coupling_sum")
