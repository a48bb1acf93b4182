"""Device milliseconds per round of the client gradient.  The objective
says how its gradient's ops are known in the trace (``grad_ops`` in its
counts): by a piece of the JAX name stack (``jvp(``/``transpose(`` for a
``jax.grad``) or by the source file of a closed-form gradient.  None where
the trace carries no such metadata."""
from chipbench import trace


def read(ctx):
    want = ctx["counts"]["grad_ops"]
    evs = [e for evs in ctx["trace"]["devices"].values() for e in evs]
    if not any(e[3].get("tf_op") or e[3].get("source_stack") for e in evs):
        return None

    def match(name, args):
        if args.get("hlo_category") in trace.CONTAINERS:
            return False
        return (any(p in args.get("tf_op", "") for p in want.get("tf_op", ()))
                or any(p in args.get("source_stack", "") for p in want.get("source", ())))

    return 1e3 * trace.device_seconds(ctx["trace"], match, ctx["window"]) / ctx["rounds"]
