"""Gap between consecutive output tokens of one request, 95th percentile
over every gap that ended inside the window.  Tokens that reach the host
in the same engine step are 0 ms apart."""
from bench.metrics import percentile


def read(run):
    rec = run.records
    gaps = [b - a for r in rec.requests
            for a, b in zip(r.token_t, r.token_t[1:]) if rec.in_window(b)]
    p = percentile(gaps, 95)
    return None if p is None else 1e3 * p
