"""Seconds from the start of the process to the start of the window:
imports, data and couplings made from the seed, engine set-up, warm-up and
any compilation."""


def read(ctx):
    return ctx.setup_s
