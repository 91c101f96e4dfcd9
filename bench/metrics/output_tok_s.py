"""Output tokens that reached the host inside the window, over the
window's length."""
from bench.metrics import window_tokens


def read(run):
    rec = run.records
    return len(window_tokens(run)) / (rec.t1 - rec.t0)
