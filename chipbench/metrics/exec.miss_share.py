"""Share of served requests that reached the executor (result-cache
misses), from the service's ``queries.served`` and
``queries.result_cache_hits`` counters over the window, in percent."""


def read(ctx):
    c = ctx["counters"]
    served = c.get("queries.served", 0)
    if not served:
        return None
    return 100.0 * (served - c.get("queries.result_cache_hits", 0)) / served
