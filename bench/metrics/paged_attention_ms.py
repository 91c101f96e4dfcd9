"""Device time of the paged-attention call per launch of the decode step
(``fused_decode``): the operations on device 0 whose scope path holds
``paged_attention`` (the scope the model puts the kernel's call under),
whatever the kernel's wrapper is called.  None where the trace has no
``program`` part (``bench/program_trace.py``) or no operation in the
scope."""
from bench.metrics import device_trace
from bench.metrics.decode_step_ms import PROGRAM

SCOPE = "paged_attention"


def read(run):
    tr = device_trace(run)
    pt = getattr(tr, "program", None)
    if pt is None:
        return None
    n, _ = tr.module_time(PROGRAM)
    secs = pt.scoped_s(tr.ops[0] if tr.ops else [], SCOPE)
    return 1e3 * secs / n if n and secs > 0 else None
