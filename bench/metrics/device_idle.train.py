"""device_idle.train: share of the traced train window in which no
operation ran on the device (union of operation intervals, averaged over
the chips), in %."""
from bench.trace import mean_busy_s


def read(win):
    if win.kind != "train" or not win.trace.devices:
        return None
    return 100.0 * (1.0 - mean_busy_s(win.trace, win.span) / win.seconds)
