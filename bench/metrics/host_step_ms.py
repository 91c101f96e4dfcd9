"""Host work per engine step: the mean, over the program's
``serve.step`` spans begun in the window, of the span's length less the
time the host waits for the device inside it, in ``serve.sync`` and in
``serve.launch`` (a launch waits once the runtime's queue is full).
None where the trace has no ``program`` part (``bench/program_trace.py``)."""


def read(run):
    pt = getattr(run.trace, "program", None)
    split = pt.split() if pt is not None else []
    return 1e3 * sum(r["host"] for r in split) / len(split) if split else None
