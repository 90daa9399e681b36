"""Share of the window in which no operation ran on the device, averaged
over the cell's devices (1 - union of op intervals / window)."""


def read(outcome, reduced, ctx):
    return reduced.idle_share()
