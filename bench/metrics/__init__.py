"""One reader per metric: ``read(run) -> float | None``.

``run`` is a ``bench.harness.RunData``.  A reader that finds nothing to
read returns None and the harness leaves the metric out of the result
line.  Helpers shared by several readers live here; a flop or byte count
lives with the metric that uses it.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile of all ``values`` (numpy's linear rule),
    or None when there are none."""
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def window_tokens(run):
    """``(request, k)`` for every output token ``k`` (0-based) that
    reached the host inside the measured window."""
    rec = run.records
    return [(r, k) for r in rec.requests
            for k, t in enumerate(r.token_t) if rec.in_window(t)]


def device_trace(run):
    """The run's trace when it saw a device with a row in the peaks
    table, else None (no device metric comes from a CPU run)."""
    tr = run.trace
    if tr is None or tr.n_devices == 0 or run.peaks is None \
            or tr.window_s <= 0:
        return None
    return tr
