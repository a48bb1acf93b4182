"""Host milliseconds per round spent in the call that enqueues the round
(the benchmark's own span around it), mean over the window."""


def read(ctx):
    d = ctx["dispatch_s"]
    return 1e3 * sum(d) / len(d) if d else None
