"""Traced window seconds over the number of engine steps begun in it,
counted from the benchmark's ``bench.step`` spans around each
``engine.step()`` (the loop calls it only while the engine has work)."""
from bench.serve_loop import STEP


def read(run):
    tr = run.trace
    if tr is None:
        return None
    n = sum(1 for e in tr.host if e.name == STEP
            and tr.window[0] <= e.start <= tr.window[1])
    return 1e3 * tr.window_s / n if n else None
