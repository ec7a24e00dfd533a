"""Mean host-clock time of one migration chunk (``svc.step()``), in
milliseconds."""


def read(ctx):
    ms = ctx["run"].chunk_ms
    return sum(ms) / len(ms) if ms else None
