"""Device milliseconds per round of the ops under the program's
``model.param_cast`` scope: the cast of the stored weights to the compute
dtype at the forward pass's entry and, inside a ``jax.grad``, its
transpose, the gradient's cast back to the state dtype (container ops left
out).  None where no op of the trace carries the scope: a program that
stores its weights in the dtype it computes in casts nothing."""
from chipbench.metrics import _scopes

SCOPE = "model.param_cast"


def read(ctx):
    match = _scopes.under(SCOPE)
    if not any(match(e[0], e[3]) for evs in ctx["trace"]["devices"].values()
               for e in evs):
        return None
    return _scopes.ms_per_round(ctx, match)
