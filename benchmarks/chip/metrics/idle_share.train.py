"""Share of the traced window, in %, in which no operation ran on the
chip, averaged over the cell's chips."""
from chipbench import trace


def read(run):
    busy = trace.mean_busy_s(run.trace, run.devices, run.lo, run.hi)
    return 100 * (1 - busy / run.window_s)
