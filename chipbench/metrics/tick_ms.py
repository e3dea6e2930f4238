"""Host wall milliseconds per tick of the service's loop
(``service/server.py`` ``_tick``: pack, dispatch, the one device_get,
scatter): ``ServiceMetrics.busy_s / ticks``, both as deltas over the
traced window.  Nothing where no tick ran."""


def read(ctx):
    c = ctx["counters"]
    if not c or not c["ticks"]:
        return None
    return 1e3 * c["busy_s"] / c["ticks"]
