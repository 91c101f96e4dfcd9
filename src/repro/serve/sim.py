"""Deterministic engine-simulation harness — first-class, not test-only.

Everything nondeterministic about serving is injected through three
fakes, so scheduler/telemetry behaviour is an exact computation instead
of a flaky wall-clock observation:

* :class:`SimClock` replaces ``time.time``/``time.perf_counter`` — both
  engines take a ``clock=`` object, so timestamps advance only when the
  trace driver says so and every submitted/finished time is an exact
  scripted value.
* :class:`FakeModel` replaces the transformer: decode is a pure-jnp
  arithmetic rule (next token = last token + 1 mod vocab), so the
  *expected* output of every request is computable in the test
  (:func:`expected_tokens`), and the shapes the engine feeds the model
  are recorded at trace time (jit traces once per shape — the recording
  IS the shape census).
* :class:`FakeCostModel` replaces calibrated pricing with a constant
  table, making the scheduler's budget arithmetic — and therefore the
  exact ``deferred_prefills`` count per step — a hand-checkable
  computation.  Its :meth:`FakeCostModel.rescale` implements the online-
  recalibration protocol (``serve.telemetry``): a drift event rescales
  the table entry it fired on, exactly like a real ``Calibration``
  update, but as one multiply.

This module started life inside ``tests/test_serve_sim.py`` (PR 4) and
was promoted here so the telemetry layer's drift/overload scenarios
(``serve.telemetry.scenarios``), the ``telemetry_replay`` campaign
experiment, and the CI smoke CLI can all drive the engines without
hardware — the tests now import the harness from here.
"""
from __future__ import annotations

import dataclasses
from collections import deque


class SimClock:
    """Injected in place of the ``time`` module: advances only on demand.

    ``time()`` and ``perf_counter()`` both read the same scripted value;
    :meth:`advance` is the only way time passes.
    """

    def __init__(self, t0: float = 0.0):
        self.t = t0

    def time(self) -> float:
        return self.t

    def perf_counter(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@dataclasses.dataclass
class _Pred:
    step_s: float


class FakeCostModel:
    """Constant (or census-derived) prices; only ``.step_s`` is consumed.

    ``decode_s`` prices the batched decode step (``predict_compiled``),
    ``prefill_s`` one analytic prefill/chunk (``predict``).  A
    ``predict_fn(census)`` overrides the constant prefill price with a
    census-derived one (e.g. proportional to flops).

    ``rescale`` is the online-recalibration hook
    (``serve.telemetry.recalibrate``): multiply the named table entry by
    ``factor`` — the fake's one-row equivalent of rescaling a
    ``Calibration`` table from live measurements.
    """

    def __init__(self, decode_s=1.0, prefill_s=1.0, predict_fn=None):
        self.decode_s = decode_s
        self.prefill_s = prefill_s
        self.predict_fn = predict_fn
        self.rescales = []          # (kind, factor) audit trail

    def predict(self, census, **kw):
        if self.predict_fn is not None:
            return _Pred(self.predict_fn(census))
        return _Pred(self.prefill_s)

    def predict_compiled(self, compiled_text, **kw):
        return _Pred(self.decode_s)

    def rescale(self, kind: str, factor: float) -> None:
        """Recalibrate one price in place: ``decode`` scales the step
        table entry, anything else the prefill/chunk entry."""
        if kind == "decode":
            self.decode_s *= factor
        else:
            self.prefill_s *= factor
        self.rescales.append((kind, factor))


class FakeModel:
    """Minimal paged-decodeable model: next token = last + 1 (mod vocab).

    ``decode_shapes`` records every (tokens, block_tables) shape pair the
    engine traces — the recorded prefill/decode shape census.
    """

    def __init__(self, vocab=97, cfg=None):
        from repro.configs import ARCHS, reduced
        self.vocab = vocab
        self.cfg = cfg if cfg is not None else reduced(
            ARCHS["gemma2-2b"], n_layers=2, vocab_size=vocab)
        self.decode_shapes = []

    def decode(self, params, cache, tokens, pos, block_tables=None):
        import jax
        self.decode_shapes.append(
            (tuple(tokens.shape),
             None if block_tables is None else tuple(block_tables.shape)))
        nxt = (tokens[:, -1] + 1) % self.vocab
        return jax.nn.one_hot(nxt, self.vocab), cache

    def param_specs(self):
        return None                 # no parameters to lay out

    def init_paged_cache(self, n_blocks, block_size, mesh=None):
        import jax
        import jax.numpy as jnp
        shape = (1, n_blocks, block_size, 1, 1)
        pool = {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16)}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            pool = jax.device_put(pool, NamedSharding(mesh, PartitionSpec()))
        return pool


def expected_tokens(prompt, n, vocab, eos_id=None):
    """What :class:`FakeModel` greedily generates for ``prompt``."""
    out, t = [], int(prompt[-1])
    for _ in range(n):
        t = (t + 1) % vocab
        out.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


def drive(engine, clock, arrivals, dt=1.0, max_steps=500):
    """Scripted-trace driver: submit each (t, prompt, max_new, eos) at its
    arrival time, stepping the engine once per clock tick.  Returns
    {rid: arrival_time} for every submitted request."""
    import numpy as np
    pending = deque(sorted(arrivals, key=lambda a: a[0]))
    rids = {}
    for _ in range(max_steps):
        while pending and pending[0][0] <= clock.t:
            t, prompt, max_new, eos = pending.popleft()
            rids[engine.submit(np.asarray(prompt, np.int32),
                               max_new_tokens=max_new, eos_id=eos)] = t
        active = engine.step()
        clock.advance(dt)
        if not pending and active == 0 and not len(engine.queue):
            break
    return rids


def work_latency_model(decode_s: float, chunk_s: float,
                       overhead_s: float = 0.0):
    """A deterministic stand-in for measured step latency: charge the
    "true" per-unit costs for the work one step record says the engine
    actually did.  ``serve.telemetry.TelemetryController`` accepts this
    as ``latency_model=`` so drift and SLO feedback loops close in
    simulation exactly as they would against a wall clock — the sim's
    ground truth replaces ``perf_counter`` deltas, which a
    :class:`SimClock` (frozen within a step) measures as zero."""

    def latency(record) -> float:
        s = overhead_s + chunk_s * record.n_prefill_units
        if record.decode_ran:
            s += decode_s
        return s

    return latency


__all__ = ["SimClock", "FakeCostModel", "FakeModel", "expected_tokens",
           "drive", "work_latency_model"]
