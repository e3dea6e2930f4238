"""Host milliseconds per tick spent waiting in the tick's one
``jax.device_get`` (``service/server.py`` ``PricingService._fetch``: the
device finishing the tick's program, then the copy to the host): ``1e3 *
device_get_s / ticks`` from the program's ``device_get_s`` and
``service_ticks`` counters, over the run.  Shared by ``.tput`` and
``.lat``.  Nothing where no tick ran or the program lacks the fetch
counter."""
from harness.program_counters import ms_per


def read(ctx):
    return ms_per("device_get_s", "service_ticks")
