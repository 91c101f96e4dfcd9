"""Time to first token, 90th percentile over every request that fell due
inside the window, from its due time to when its first token reached
the host.  A request that never got one counts with its wait until the
run ended (a lower bound)."""
from bench.metrics import percentile


def read(run):
    rec = run.records
    waits = [(r.token_t[0] if r.token_t else rec.t_end) - r.due
             for r in rec.due_in_window()]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
