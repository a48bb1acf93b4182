"""Device milliseconds per round of the client gradient, read from the
program's ``round.client_grad`` scope (container ops left out): the
scope-read twin of ``client_grad_ms``.  It holds the pytree/arena
round trip (``arena_pack``) of a model's gradient too.  None where the
program names no round phase."""
from chipbench.metrics import _scopes


def read(ctx):
    if not _scopes.scoped(ctx):
        return None
    return _scopes.ms_per_round(ctx, _scopes.under("/round.client_grad/"))
