"""Share of its HBM roofline that the uplink kernel reaches: read xbar and
lam once, the server row once, write the uplink once, at the chip's HBM
bandwidth, over the kernel's device time in the trace."""
from chipbench.metrics._kernels import roofline


def read(ctx):
    return roofline(ctx, "round_tail")
