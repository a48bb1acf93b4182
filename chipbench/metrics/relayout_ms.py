"""Device milliseconds per round of the reshapes, pads and slices between
the ``(m, width)`` arenas and the ``(m, rows, 128)`` blocks of the Pallas
calls: the ops whose name stack holds the program's ``relayout`` scope.
None where the program names no round phase."""
from chipbench.metrics import _scopes


def read(ctx):
    if not _scopes.scoped(ctx):
        return None
    return _scopes.ms_per_round(ctx, _scopes.under("/relayout/"))
