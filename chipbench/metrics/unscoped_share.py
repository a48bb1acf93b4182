"""Share of the window's device busy time spent in ops that run under no
round phase of the program (no ``round.`` scope in their name stack), in
%: what the phase attribution misses, such as the benchmark's own batch
slicing, the server row's pack, and ops XLA makes with no name stack.
None where the program names no round phase."""
from chipbench import trace
from chipbench.metrics import _scopes


def read(ctx):
    if not _scopes.scoped(ctx):
        return None
    busy = ctx["reduced"]["busy_s"]
    if busy <= 0:
        return None

    def match(name, args):
        return (args.get("hlo_category") not in trace.CONTAINERS
                and _scopes.PHASE not in args.get("tf_op", ""))

    return 100.0 * trace.device_seconds(ctx["trace"], match, ctx["window"]) / busy
