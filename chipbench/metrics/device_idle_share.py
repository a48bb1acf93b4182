"""Share of the traced window in which no operation ran on the device, in
%, mean over the cell's devices."""


def read(ctx):
    share = ctx["reduced"]["idle_share"]
    return None if share is None else 100.0 * share
