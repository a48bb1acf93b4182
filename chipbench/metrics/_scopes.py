"""Shared by the readers of the program's own name scopes.

The program runs each phase of a round under a ``jax.named_scope``
(``round.inner_loop``, ``round.client_grad``, ``round.client_update``,
``round.uplink``, ``round.server_mean``, ``round.dual_refresh``,
``round.metrics``), each arena kernel under its op's name, and every
reshape, pad and slice around a Pallas call under ``relayout``.  A scope
lands in the JAX name stack of each device op (``tf_op`` in the trace),
for example ``jit(one_round)/round.inner_loop/while/body/closed_call/
round.client_update/fused_update_arena/relayout/jit(_pad)/pad``.  A program
that names no phase gives no reading: the readers return None.
"""
from __future__ import annotations

from chipbench import trace

PHASE = "round."


def scoped(ctx) -> bool:
    """Whether some device op of the trace runs under a round phase."""
    return any(PHASE in e[3].get("tf_op", "")
               for evs in ctx["trace"]["devices"].values() for e in evs)


def under(part: str):
    """Match the ops whose name stack holds ``part``, containers left out."""

    def match(name, args):
        return (args.get("hlo_category") not in trace.CONTAINERS
                and part in args.get("tf_op", ""))

    return match


def ms_per_round(ctx, match) -> float:
    return 1e3 * trace.device_seconds(ctx["trace"], match, ctx["window"]) / ctx["rounds"]
