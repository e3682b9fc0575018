"""Share of the traced window in which no op ran on the device (closed-loop
batch cells): 1 - union of the device's op intervals / window, averaged over
the chips used."""


def read(ctx):
    return ctx.idle_share()
