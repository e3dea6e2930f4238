"""Host milliseconds of admission per request that entered the queue
(``service/server.py`` ``PricingService._admit``: validation, lowering,
the result-cache lookup, the scheduler's admit, the journal write):
``1e3 * admit_s / admitted`` from the program's ``service_admit_s`` and
``service_admitted`` counters, over the run.  Cache hits and rejections
are not counted.  Nothing where no request was enqueued or the program
lacks the counters."""
from harness.program_counters import ms_per


def read(ctx):
    return ms_per("service_admit_s", "service_admitted")
