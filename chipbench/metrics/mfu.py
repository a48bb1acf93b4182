"""Model FLOPs of a round (forward and backward work the objective needs,
no recomputation) over the round's wall time times the chips' bf16 peak,
in %."""


def read(ctx):
    return 100.0 * ctx["counts"]["flops_per_round"] / (
        ctx["round_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"])
