"""Share of its HBM roofline that the eq. (20) client update kernel
reaches: the least bytes the update needs (read x, g and lam once, the
server row once, write x once) at the chip's HBM bandwidth, over the
kernel's device time in the trace."""
from chipbench.metrics._kernels import roofline


def read(ctx):
    return roofline(ctx, "fused_update_arena")
