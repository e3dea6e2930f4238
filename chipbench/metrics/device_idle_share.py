"""Share of the traced window in which no operation ran on the device,
in percent: ``100 * (1 - busy_s / window_s)`` from the profiler trace
(see ``harness/reduce.py``).  Nothing without a device trace."""


def read(ctx):
    red = ctx["trace"]
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
