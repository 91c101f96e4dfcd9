"""Share of the traced window in which no operation ran on the device:
1 - (union of operation intervals) / window, averaged over devices."""
from bench.metrics import device_trace


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
