"""Share of the window's query-profile lookups that the facade served from
its cache, in percent: ``cache.profile_hits`` over ``cache.profile_hits``
and ``cache.profile_builds`` (each build a host execution of the query by
``profile_from_plan``). None when no round looked a profile up, or when
the program keeps no such counters."""


def read(ctx):
    c = ctx["counters"]
    hits = c.get("cache.profile_hits", 0)
    total = hits + c.get("cache.profile_builds", 0)
    return 100.0 * hits / total if total else None
