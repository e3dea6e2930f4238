"""Share of the chunk slots that carried a request's row, in percent:
``ServiceMetrics.slots_used / slots_total`` as deltas over the traced
window (``service/scheduler.py`` coalescing).  Nothing where no tick
ran."""


def read(ctx):
    c = ctx["counters"]
    if not c or not c["slots_total"]:
        return None
    return 100.0 * c["slots_used"] / c["slots_total"]
