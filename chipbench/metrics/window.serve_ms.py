"""Mean host-clock time a pass spent in its ``serve_window`` calls, over
the passes in which at least one request missed the result cache, in
milliseconds."""


def read(ctx):
    ms = [w.serve_ms for w in ctx["run"].windows if w.misses]
    return sum(ms) / len(ms) if ms else None
