"""Device time of the expert layer's grouped matmul per engine step: the
summed device time of its operations in the traced window
(``expert_ffn_roofline.KERNEL``) over the engine steps begun in it
(the benchmark's ``bench.step`` spans)."""
from bench.metrics import device_trace
from bench.metrics.expert_ffn_roofline import kernel_time
from bench.serve_loop import STEP


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    steps = sum(1 for e in tr.host if e.name == STEP
                and tr.window[0] <= e.start <= tr.window[1])
    n, secs = kernel_time(tr)
    return 1e3 * secs / steps if n and steps else None
