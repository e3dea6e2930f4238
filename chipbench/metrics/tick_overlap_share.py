"""Share of the service's ticks that were dispatched while another tick
was in flight, in percent (``service/server.py`` ``PricingService._tick``
keeps one fused tick in flight and dispatches the next behind it):
``100 * service_ticks_overlapped / service_ticks`` from the program's
counters, over the run.  Nothing where no tick ran or the program lacks
the overlap counter."""


def read(ctx):
    from repro.obs.registry import REGISTRY

    overlapped = REGISTRY.get("service_ticks_overlapped")
    ticks = REGISTRY.get("service_ticks")
    if overlapped is None or ticks is None or not ticks.get():
        return None
    return 100.0 * overlapped.get() / ticks.get()
