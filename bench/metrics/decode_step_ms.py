"""Mean device time of one launch of the engine's jitted decode step
(``fused_decode``), from the trace's program events."""
from bench.metrics import device_trace

PROGRAM = "fused_decode"


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    n, secs = tr.module_time(PROGRAM)
    return 1e3 * secs / n if n else None
