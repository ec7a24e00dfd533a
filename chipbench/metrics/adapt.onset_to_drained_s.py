"""Mean over the window's rounds of the seconds from a round's onset to
the end of the pass in which its migration session drained, or to its
phase's end where that came first."""


def read(ctx):
    rounds = ctx["run"].rounds
    if not rounds:
        return None
    return sum(min(r.end_s if r.drained_s is None else r.drained_s, r.end_s)
               - r.onset_s for r in rounds) / len(rounds)
