"""The spans the paged serving engine writes into the profiler's trace.

Each name is a ``jax.profiler.TraceAnnotation`` opened on the host, so
it lands in the same trace as the device's planes, on the same clock:
a reduction of the trace can put every device step and idle gap under
what the host was doing.  With the profiler off a span costs only the
annotation's no-op (about a microsecond); the spans are always on and
have no switch.

The host waits for the device in two places: ``SYNC``, and ``LAUNCH``
once the runtime's queue of launched programs is full.  Host work is a
step less those two.

Unlike the package root and :mod:`~.metrics`, this module imports jax.
"""
from __future__ import annotations

import functools

from jax.profiler import annotate_function

# one engine iteration (``_step``): the interval ``StepRecord.measured_s``
# times.  At its end it carries the stat ``rows``, the rows the decode
# dispatch stepped (rows mid-prefill ride along masked and do not count),
# and for an MoE model ``expert_pairs`` and ``expert_max_load``: the
# expert counts of the launches its sync brought back (the previous
# iteration's chunks and decode, counted on the device and carried out
# beside the tokens, so they cost no sync of their own)
STEP = "serve.step"
# the scheduler's plan for the iteration, with its cost-model pricing
PLAN = "serve.plan"
# one prefill chunk: block growth, then UPLOAD and LAUNCH
CHUNK = "serve.chunk"
# the batched decode: block growth, then UPLOAD and LAUNCH
DECODE = "serve.decode"
# a chunk's or decode's operands put on the device: the per-call arrays
# and, when a row's blocks changed, the block tables
UPLOAD = "serve.upload"
# the programs a chunk or decode hands to the runtime (a chunk's own row
# of the block table is one); it returns at once unless the runtime's
# queue of launches is full, and then waits for the device
LAUNCH = "serve.launch"
# the host blocked in ``jax.device_get`` waiting for the device: the
# interval ``StepRecord.sync_s`` sums
SYNC = "serve.sync"
# one retirement, compaction included
RETIRE = "serve.retire"


def traced(name: str):
    """Decorator: run the method inside the span ``name``."""
    return functools.partial(annotate_function, name=name)
