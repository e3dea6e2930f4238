"""Host milliseconds per raw tick from entering the raw lane's tick to its
dispatch (``service/server.py`` ``PricingService._tick_raw``: packing the
raw spec groups, shedding what does not fit, padding, and the move of the
padded tables to the device): ``1e3 * raw_pack_s / raw_packs`` from the
program's ``service_raw_pack_s`` and ``service_raw_packs`` counters, over
the run.  Nothing where no raw tick ran or the program lacks the
counters."""
from harness.program_counters import ms_per


def read(ctx):
    return ms_per("service_raw_pack_s", "service_raw_packs")
