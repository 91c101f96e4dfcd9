"""Rows a decode dispatch steps: the mean of the ``rows`` count that
the program's ``serve.step`` spans carry, over the steps begun in the
window that dispatched a decode.  Rows still in prefill ride along
masked and do not count.  None where the trace has no ``program`` part
(``bench/program_trace.py``)."""


def read(run):
    pt = getattr(run.trace, "program", None)
    rows = [s.stats["rows"] for s in (pt.steps() if pt is not None else [])
            if s.stats.get("rows")]
    return sum(rows) / len(rows) if rows else None
