"""Mean host-clock time of the window's adaptation rounds (``svc.adapt``
at each phase's onset), in milliseconds."""


def read(ctx):
    rounds = ctx["run"].rounds
    return sum(r.adapt_ms for r in rounds) / len(rounds) if rounds else None
