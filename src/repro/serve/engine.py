"""Batched serving engines: slot-granular continuous batching, and the
paged engine that replaces per-slot ``max_len`` KV stripes with a shared
block pool.

``ServingEngine`` is the vLLM-style loop reduced to its scheduling core
with slot-granular KV memory: every admitted sequence reserves a full
``max_len`` stripe of the batch cache, so KV bytes resident are always
``max_batch x max_len`` regardless of actual context lengths.

``PagedServingEngine`` replaces that with a paged subsystem:

* the KV store is a fixed pool of blocks (``serve.paging``) gathered
  through per-request block tables — resident KV bytes are
  ``n_blocks x block_size``, sized to the *traffic*, not to the
  worst-case ``max_batch x max_len`` rectangle;
* admission is a policy object (``serve.scheduler``): prompts prefill in
  fixed-size chunks interleaved with decode steps, each chunk priced via
  the cost model so the iteration respects ``step_budget_s``;
* when the pool runs out, the youngest placed request is preempted —
  its blocks freed, the request re-enqueued at the queue front — and
  replayed later (greedy decode is deterministic, so eviction never
  changes tokens); the oldest placed request is never evicted, which
  guarantees forward progress;
* on retire, freed blocks may leave gaps; copy-on-retire compaction
  moves the allocated blocks down to the lowest ids (one gather-then-
  scatter copy) so the touched span of the pool stays dense.

The fused decode hot path (``fused=True``, the default)
-------------------------------------------------------
Both engines rebuild their per-step traffic around one fused, donated,
pipelined device step:

* **on-device sampling** — greedy argmax runs inside the jitted step
  (``Model.decode_step``), so ``[B]`` int32 tokens cross to host per
  step instead of a ``[B, vocab]`` logit matrix materialized at the step
  boundary for eager host-side sampling;
* **donated caches** — the KV cache (slot stripes or the paged pool) is
  donated on both the ``jax.jit`` and ``.lower().compile()`` paths, so
  a step updates it in place instead of materializing a second cache
  (halves peak KV memory, removes a full-cache HBM round-trip per step);
  prefill splices and admission writes donate the same way;
* **device-resident loop state** — tokens stay on device between steps
  (updated by the step itself / jitted scatters on admission), and the
  paged block tables upload once per *mutation*, not per step;
* **one-step-ahead pipelining** — step N+1 is dispatched *before* step
  N's tokens are synced, so host bookkeeping (retire / admit / schedule)
  runs in the shadow of the device step.  The step additionally echoes
  its *input* tokens (a ``[2, B]`` array: inputs + outputs), so a
  prefill's first token reaches ``Request.tokens`` through the same
  single per-step sync instead of its own transfer.  Retirement and
  admission therefore lag the device by exactly one step — token
  streams per request are unchanged (greedy decode is deterministic and
  per-row state is independent), the retired row just rides along for
  one masked/overwritten "shadow" step whose outputs are dropped.

``fused=False`` keeps the legacy blocking path (fresh host uploads per
step, the ``[B, vocab]`` logit output pulled through an eager argmax +
blocking sync, undonated caches) — the baseline the
``decode_hotpath`` campaign experiment measures against.

All device->host reads go through ``_sync`` (counted in
``EngineStats.host_syncs`` and performed with the *explicit*
``jax.device_get``), so a test can run an engine under
``jax.transfer_guard_device_to_host("disallow")`` and prove the fused
path performs no stray transfers and at most one sync per step.

Both engines price admission with a ``repro.core.costmodel.CostModel``
when one is supplied, install an ``repro.core.autotune.Autotuner`` handle
for the duration of each step, and accept an injectable ``clock`` (any
object with ``time()``/``perf_counter()``) so the simulation test harness
can drive them on a deterministic fake clock.
"""
from __future__ import annotations

import dataclasses
import itertools
import time as _time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.costmodel.model import CostModel, Prediction
from repro.models.layers.moe import EXPERT_MAX, EXPERT_PAIRS
from repro.models.zoo import Model, fused_decode_step
from repro.serve.paging import (BlockAllocator, blocks_for_tokens,
                                remap_table)
from repro.serve.scheduler import ChunkedPrefillScheduler
from repro.serve.telemetry import spans
from repro.sharding import ctx


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine:
    tokens: List[int] = dataclasses.field(default_factory=list)
    submitted_s: float = 0.0
    first_token_s: Optional[float] = None   # clock.time() at first booking
    finished_s: float = 0.0


@dataclasses.dataclass
class EngineStats:
    """Cumulative per-engine counters, exposed as ``engine.stats``.

    Field-by-field meaning (units, healthy ranges, how they differ from
    the per-step telemetry records) is documented in
    ``docs/ops-runbook.md``; the telemetry layer
    (``serve.telemetry.metrics.StepRecord``) snapshots several of these
    counters per step so consumers can diff consecutive records for
    rates.
    """
    steps: int = 0
    prefills: int = 0               # completed prefills (net of evictions)
    decoded_tokens: int = 0         # DELIVERED tokens (eviction replays
    #                                 are rolled back, not double-counted)
    completed: int = 0
    deferred_prefills: int = 0      # admissions pushed to a later step
    host_syncs: int = 0             # device->host transfers (via _sync)
    table_uploads: int = 0          # block-table host->device uploads
    # paged-engine extensions (stay 0/empty on the slot engine)
    prefill_chunks: int = 0         # chunked-prefill calls run
    preemptions: int = 0            # evictions (blocks reclaimed, re-enqueued)
    compactions: int = 0            # copy-on-retire block compactions
    peak_blocks_in_use: int = 0
    admission_order: List[int] = dataclasses.field(default_factory=list)
    integrity_failures: int = 0     # corrupted fused-step drains dropped
    # an MoE model's expert layer (fused paged path; 0 for a dense model):
    # (token, held expert) pairs computed, summed over launches and
    # layers, and the most pairs one held expert got in any launch
    expert_pairs: int = 0
    expert_max_load: int = 0


def _analytic_prefill_prediction(cost_model: CostModel, cfg,
                                 n_tokens: int) -> Prediction:
    """Price a prefill of ``n_tokens`` ANALYTICALLY (``costmodel.
    analytic``), not by compiling it — admission runs per engine step and
    a per-length XLA compile there would stall serving for pure
    bookkeeping.  THE one implementation both engines' cached
    ``_predict_*`` methods wrap, so slot and paged admission can never
    silently price the same prompt differently."""
    from repro.configs.base import ShapeCell
    from repro.core.costmodel.analytic import analytic_census
    cell = ShapeCell("admission", "prefill", n_tokens, 1)
    return cost_model.predict(analytic_census(cfg, cell, n_devices=1,
                                              n_model=1))


def _decode_step_fn(model):
    """``Model.decode_step`` when the model ships one, else the same
    fusion built from ``model.decode`` (the simulation harness's fake
    models only define ``decode``)."""
    if getattr(model, "decode_step", None) is not None:
        return model.decode_step
    return fused_decode_step(model.decode)


def _split_step(out):
    """A decode step's ``(next_tokens, cache, counts)``: a dense model's
    step returns no counts (``Model.decode_step``)."""
    return (*out, {}) if len(out) == 2 else out


def _echo_ok(arr: np.ndarray) -> bool:
    """Per-step integrity probe over the synced ``[2, B]`` token echo.

    Token ids are non-negative by construction (argmax indices; masked
    rows echo their input), so any negative or non-finite value in the
    drained array means the step's output is corrupt — NaN logits argmax
    into garbage, and a poisoned device buffer shows up directly.  The
    check is host-side on the array the drain already paid to sync, so
    the probe adds zero device work and zero extra transfers."""
    a = np.asarray(arr)
    return bool(np.isfinite(a).all() and (a >= 0).all())


class _TunedDispatch:
    """Shared ``step()`` shell: install the engine's autotuner handle for
    the duration of one ``_step()`` so tuned=True kernel lookups hit this
    engine's cache without leaking a process-global handle.

    Also hosts the telemetry/recalibration surface both engines share:
    ``_step_budget`` (SLO token bucket else static budget) and
    ``set_cost_model`` (the online-recalibration swap point)."""

    autotuner = None
    telemetry = None
    _sync_s = 0.0           # seconds this iteration blocked in _sync

    def step(self) -> int:
        if self.autotuner is not None:
            from repro.core import autotune as autotune_mod
            with autotune_mod.using(self.autotuner):
                return self._step()
        return self._step()

    def _sync(self, x) -> np.ndarray:
        """THE device->host boundary: every value an engine reads back
        crosses here (explicit ``jax.device_get``, counted), so the
        transfer-guard test can disallow every other transfer.  The
        wait is the span ``serve.sync`` and sums into ``_sync_s``."""
        self.stats.host_syncs += 1
        t = self._clock.perf_counter()
        with TraceAnnotation(spans.SYNC):
            host = jax.device_get(x)
        self._sync_s += self._clock.perf_counter() - t
        return jax.tree.map(np.asarray, host)

    def _book_first_token(self, req: Request, tok: int) -> None:
        """Append a request's first token; a replay after eviction keeps
        the time of the first booking."""
        if req.first_token_s is None:
            req.first_token_s = self._clock.time()
        req.tokens.append(tok)

    def _step_budget(self) -> Optional[float]:
        """The effective admission budget for this iteration: the SLO
        token bucket when the telemetry controller carries one (refilled
        here — call once per iteration), else the static
        ``step_budget_s``.  The returned number feeds the exact same
        gate arithmetic either way."""
        if self.telemetry is not None:
            budget = self.telemetry.begin_step()
            if budget is not None:
                return budget
        return self.step_budget_s

    def set_cost_model(self, cost_model) -> None:
        """Swap the pricing model in place (online recalibration).

        Clears the prediction cache so every later admission re-prices
        against the new tables; the decode step itself is already an AOT
        executable, and ``_decode_text`` (the compiled HLO captured at
        first pricing) lets ``_predict_decode`` re-price it without
        re-lowering."""
        self.cost_model = cost_model
        self._pred_cache.clear()


class ServingEngine(_TunedDispatch):
    """Slot-granular continuous batching (see module docstring)."""

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_len: int = 512,
                 cost_model: Optional[CostModel] = None,
                 step_budget_s: Optional[float] = None,
                 autotuner=None, clock=None, fused: bool = True,
                 telemetry=None):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.cost_model = cost_model
        self.step_budget_s = step_budget_s
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.bind(self)
        # tuned kernel dispatch: the handle is installed for the duration
        # of each step() so the model's use_pallas hot paths (tuned=True
        # lookups) hit this engine's cache without leaking a process-global
        # handle past the engine's own iterations
        self.autotuner = autotuner
        self._clock = clock if clock is not None else _time
        self.fused = fused
        self.queue: deque[Request] = deque()
        self.done: Dict[int, Request] = {}
        self.stats = EngineStats()
        self._rid = itertools.count()
        # slot state
        self.cache = model.init_cache(max_batch, max_len)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)
        self.slot_tok = np.zeros(max_batch, np.int32)
        self._pred_cache: Dict = {}
        self._decode_text: Optional[str] = None
        self._pending = None
        step_fn = _decode_step_fn(model)
        if fused:
            # device-resident loop state: the step consumes and reproduces
            # it, so nothing but the [2,B] token echo crosses to host
            self._toks = jnp.zeros((max_batch,), jnp.int32)
            self._pos = jnp.zeros((max_batch,), jnp.int32)

            def fused_step(params, cache, toks, pos):
                nxt, cache, _ = _split_step(
                    step_fn(params, cache, toks[:, None], pos))
                io = jnp.stack([toks, nxt])      # input echo + outputs
                return io, nxt, pos + 1, cache

            def admit_write(cache, cache1, logits, toks, pos, slot, start):
                def splice(big, small):
                    return jax.lax.dynamic_update_slice_in_dim(
                        big, small.astype(big.dtype), slot, axis=1)
                cache = jax.tree.map(splice, cache, cache1)
                tok0 = jnp.argmax(logits[0]).astype(jnp.int32)
                return (cache, toks.at[slot].set(tok0),
                        pos.at[slot].set(start))

            self._decode = jax.jit(fused_step, donate_argnums=(1,))
            self._admit_fn = jax.jit(admit_write, donate_argnums=(0, 3, 4))
        else:
            self._decode = jax.jit(model.decode)

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               submitted_s: Optional[float] = None) -> int:
        """Enqueue one request.  ``submitted_s`` is the external-admission
        hook: the cluster router (``serve.cluster``) re-submits a
        re-routed request with its ORIGINAL arrival time so per-request
        latency accounting survives the move; the default stamps this
        engine's clock."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) >= self.max_len:
            raise ValueError(f"prompt of {len(prompt)} tokens cannot fit "
                             f"max_len={self.max_len} (needs >= 1 decode "
                             "slot)")
        rid = next(self._rid)
        self.queue.append(Request(rid, prompt, max_new_tokens, eos_id,
                                  submitted_s=self._clock.time()
                                  if submitted_s is None else submitted_s))
        return rid

    def kv_cache_bytes(self) -> int:
        """Resident bytes of the decode cache (the full preallocated
        ``max_batch x max_len`` stripe set, by construction).  With
        ``fused=True`` this is also the *peak*: steps donate the cache
        and update it in place, so no second copy ever materializes."""
        return int(sum(x.nbytes for x in jax.tree.leaves(self.cache)))

    # -- cost-model pricing ---------------------------------------------------
    def _predict_decode(self) -> Prediction:
        """Price one decode step (fixed shape: the padded max_batch).  The
        AOT executable this compiles REPLACES the jitted decode fn — jit's
        dispatch cache would not reuse it, and the decode shapes never
        change — so pricing costs no extra compilation.  Donation carries
        through ``.lower().compile()``, so the AOT path updates the cache
        in place exactly like the jitted one.

        The compiled HLO text is kept (``_decode_text``) so a
        recalibration (``set_cost_model`` clearing ``_pred_cache``) can
        re-price the step without re-lowering — the executable has no
        ``.lower`` once AOT-compiled."""
        key = ("decode", self.max_batch)
        if key not in self._pred_cache:
            if self._decode_text is None:
                pos = jnp.zeros((self.max_batch,), jnp.int32)
                if self.fused:
                    toks = jnp.zeros((self.max_batch,), jnp.int32)
                else:
                    toks = jnp.zeros((self.max_batch, 1), jnp.int32)
                compiled = self._decode.lower(self.params, self.cache,
                                              toks, pos).compile()
                self._decode_text = compiled.as_text()
                self._decode = compiled
            self._pred_cache[key] = self.cost_model.predict_compiled(
                self._decode_text)
        return self._pred_cache[key]

    def _predict_prefill(self, prompt_len: int) -> Prediction:
        """Price one prefill at this prompt length (cached per length);
        see ``_analytic_prefill_prediction`` for why this never
        compiles."""
        key = ("prefill", prompt_len)
        if key not in self._pred_cache:
            self._pred_cache[key] = _analytic_prefill_prediction(
                self.cost_model, self.model.cfg, prompt_len)
        return self._pred_cache[key]

    # -- internals ------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self) -> "tuple[float, int, Optional[float]]":
        """Pack queued prefills into free slots; returns ``(planned,
        admitted, budget)``: the predicted time of this engine iteration
        (0.0 when no cost model is attached), the number of prefills
        admitted, and the budget the gate used (None when ungated).

        With a cost model + budget, admission stops once the predicted
        iteration time (decode step + admitted prefills) would exceed the
        budget — but always admits at least one prefill when a slot is
        free, so the engine cannot starve on an over-tight budget.  The
        budget is ``step_budget_s`` (static) or the SLO token bucket's
        per-step allowance when a telemetry controller carries one
        (``_step_budget``) — same arithmetic, adaptive number."""
        budget = self._step_budget()
        gated = self.cost_model is not None and budget is not None
        planned = self._predict_decode().step_s \
            if self.cost_model is not None else 0.0
        admitted = 0
        free = self._free_slots()
        for idx, slot in enumerate(free):
            if not self.queue:
                break
            if self.cost_model is not None:
                pre_s = self._predict_prefill(
                    len(self.queue[0].prompt)).step_s
                if gated and admitted > 0 \
                        and planned + pre_s > budget:
                    # deferral accounting: walk the queued requests a free
                    # slot could still have taken this step and count ONLY
                    # those whose own predicted prefill would not have fit
                    # in the remaining budget.  Requests blocked purely by
                    # FIFO order behind an over-budget head (they would
                    # have fit) are waiting on ordering, not on the
                    # budget, and are not counted.
                    for q in itertools.islice(self.queue, len(free) - idx):
                        q_s = self._predict_prefill(len(q.prompt)).step_s
                        if planned + q_s > budget:
                            self.stats.deferred_prefills += 1
                    break
                planned += pre_s
            self._prefill_into_slot(slot, self.queue.popleft())
            admitted += 1
        return planned, admitted, budget

    def _prefill_into_slot(self, slot: int, req: Request):
        """Prefill a single request and splice its KV into the batch cache.

        Fused mode: the splice, the first-token argmax and the device
        token/pos scatter run in ONE jitted call with the batch cache and
        the loop-state arrays donated — admission is an in-place slot
        write, not a full new cache tree, and nothing crosses to host
        (the first token reaches ``req.tokens`` through the next step's
        input echo)."""
        S = len(req.prompt)
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        logits, cache1 = self.model.prefill(self.params, batch,
                                            max_len=self.max_len)
        if self.fused:
            self.cache, self._toks, self._pos = self._admit_fn(
                self.cache, cache1, logits, self._toks, self._pos,
                jnp.asarray(slot, jnp.int32), jnp.asarray(S, jnp.int32))
        else:
            def splice(big, small):
                return big.at[:, slot:slot + 1].set(small.astype(big.dtype))
            self.cache = jax.tree.map(splice, self.cache, cache1)
            self.slot_tok[slot] = int(self._sync(jnp.argmax(logits[0])))
            self._book_first_token(req, int(self.slot_tok[slot]))
        self.slot_req[slot] = req
        self.slot_pos[slot] = S
        self.stats.prefills += 1
        self.stats.admission_order.append(req.rid)

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        req.finished_s = self._clock.time()
        self.done[req.rid] = req
        self.slot_req[slot] = None
        self.stats.completed += 1
        if self.telemetry is not None:
            self.telemetry.on_retire(req)

    def _drain(self, pending) -> None:
        """Sync and book one in-flight step: append its tokens (plus the
        echoed prefill token for rows on their first decode), advance the
        host position mirror, retire.  Rows whose slot changed hands
        since dispatch were retired in an earlier drain — their shadow
        tokens are dropped."""
        if pending is None:
            return
        io, snap = pending
        arr = self._sync(io)                 # the ONE transfer of the step
        if not _echo_ok(arr):
            # corrupted step: drop the whole drain rather than book
            # garbage tokens — the supervisor reads this counter's delta
            # and fails the replica (requests are reclaimed by prompt)
            self.stats.integrity_failures += 1
            return
        in_t, out_t = arr[0], arr[1]
        for i, req in snap:
            if self.slot_req[i] is not req:
                continue                     # shadow step of a retired row
            if not req.tokens:
                self._book_first_token(req, int(in_t[i]))  # prefill's first
            req.tokens.append(int(out_t[i]))
            self.stats.decoded_tokens += 1
            self.slot_pos[i] += 1
            hit_eos = req.eos_id is not None and req.tokens[-1] == req.eos_id
            out_of_budget = len(req.tokens) >= req.max_new_tokens
            out_of_cache = self.slot_pos[i] >= self.max_len - 1
            if hit_eos or out_of_budget or out_of_cache:
                self._retire(i)

    def _step_record(self, planned: float, measured: float, n_active: int,
                     admitted: int, budget: Optional[float]):
        """One telemetry ``StepRecord`` for this iteration (the slot
        engine dispatches a decode whenever any slot is occupied, so
        ``decode_ran`` is simply ``n_active > 0``)."""
        from repro.serve.telemetry.metrics import StepRecord
        pred = self._pred_cache.get(("decode", self.max_batch))
        return StepRecord(
            engine="slot", step=self.stats.steps, t_s=self._clock.time(),
            n_active=n_active, queue_depth=len(self.queue),
            predicted_s=planned,
            predicted_decode_s=pred.step_s if pred else 0.0,
            measured_s=measured, decode_ran=n_active > 0,
            n_prefill_units=admitted,
            bottleneck=getattr(pred, "bottleneck", ""),
            budget_s=budget if budget is not None else 0.0,
            host_syncs=self.stats.host_syncs,
            table_uploads=self.stats.table_uploads,
            blocks_in_use=0, n_blocks=0,
            decoded_tokens=self.stats.decoded_tokens,
            preemptions=0, deferred=self.stats.deferred_prefills,
            kernel_splits=0,
            integrity_failures=self.stats.integrity_failures,
            sync_s=self._sync_s)

    def _step(self) -> int:
        """One engine iteration.  Returns #active at dispatch time.
        (``step()`` — the public entry — is the autotuner-installing shell
        inherited from ``_TunedDispatch``.)

        Fused: admit (host work in the shadow of the in-flight step),
        dispatch step N, then drain step N-1 — the sync of a step's
        tokens always happens after the NEXT step is on the device."""
        if not self.fused:
            return self._step_blocking()
        t0, self._sync_s = self._clock.perf_counter(), 0.0
        prev, self._pending = self._pending, None
        planned, admitted, budget = self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if active:
            io, nxt, pos, self.cache = self._decode(
                self.params, self.cache, self._toks, self._pos)
            self._toks, self._pos = nxt, pos
            self._pending = (io, [(i, self.slot_req[i]) for i in active])
            self.stats.steps += 1
        self._drain(prev)
        measured = self._clock.perf_counter() - t0
        if active and self.telemetry is not None:
            self.telemetry.on_step(self._step_record(
                planned, measured, len(active), admitted, budget))
        return len(active)

    def _step_blocking(self) -> int:
        """The legacy (unfused) iteration: fresh uploads, the [B, vocab]
        logits synced, undonated cache — the decode_hotpath baseline."""
        t0, self._sync_s = self._clock.perf_counter(), 0.0
        planned, admitted, budget = self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        toks = jnp.asarray(self.slot_tok[:, None])
        pos = jnp.asarray(self.slot_pos)
        logits, self.cache = self._decode(self.params, self.cache, toks, pos)
        nxt = self._sync(jnp.argmax(logits, axis=-1)).astype(np.int32)
        self.stats.steps += 1
        measured = self._clock.perf_counter() - t0
        if self.telemetry is not None:
            self.telemetry.on_step(self._step_record(
                planned, measured, len(active), admitted, budget))
        for i in active:
            req = self.slot_req[i]
            req.tokens.append(int(nxt[i]))
            self.stats.decoded_tokens += 1
            self.slot_tok[i] = nxt[i]
            self.slot_pos[i] += 1
            hit_eos = req.eos_id is not None and nxt[i] == req.eos_id
            out_of_budget = len(req.tokens) >= req.max_new_tokens
            out_of_cache = self.slot_pos[i] >= self.max_len - 1
            if hit_eos or out_of_budget or out_of_cache:
                self._retire(i)
        return len(active)

    def run_until_done(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            active = self.step()
            if active == 0 and not self.queue:
                break
        if self._pending is not None:        # max_steps exhausted mid-flight
            self._drain(self._pending)
            self._pending = None
        return self.stats


# ---------------------------------------------------------------------------
# the paged engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Row:
    """One decode row of the paged batch: the request it serves plus its
    prefill progress.  The row's block table lives in the engine's
    ``block_tables`` array (row-indexed), not here."""
    req: Request
    filled: int = 0                 # prompt tokens whose K/V are written
    ready: bool = False             # prefill complete; decodes each step
    pos: int = 0                    # context length == next write position
    last_tok: int = 0               # legacy path only; fused keeps it on device
    dispatched: int = 0             # fused: decode dispatches incl. in-flight


def paged_decode_fn(step_fn, mesh=None):
    """The paged engine's fused decode step before jit: one greedy token
    per row, the ``[2, B]`` echo of inputs and outputs, masked rows
    (``pos < 0``) keeping their resident token, and the step's expert
    counts (an empty dict for a dense model).  With a replica ``mesh``
    the paged-attention kernel, which GSPMD cannot partition, runs per
    shard (``sharding.ctx.use_kernel_mesh``)."""
    def fused_decode(params, cache, toks, pos, bt):
        with ctx.use_kernel_mesh(mesh):
            nxt, cache, counts = _split_step(
                step_fn(params, cache, toks[:, None], pos, bt))
        io = jnp.stack([toks, nxt])
        return io, jnp.where(pos >= 0, nxt, toks), cache, counts
    return fused_decode


def paged_chunk_fn(step_fn, mesh=None):
    """The paged engine's fused prefill-chunk step before jit: only a
    prompt's FINAL chunk writes its first token into the device token
    array; intermediate chunks leave the row's slot untouched.  Its
    expert counts come out beside them."""
    def fused_chunk(params, cache, toks, start, bt, toks_dev, idx, final):
        with ctx.use_kernel_mesh(mesh):
            nxt, cache, counts = _split_step(
                step_fn(params, cache, toks, start, bt))
        tok0 = jnp.where(final, nxt[0], toks_dev[idx])
        return cache, toks_dev.at[idx].set(tok0), counts
    return fused_chunk


class PagedServingEngine(_TunedDispatch):
    """Continuous batching over a paged KV cache with chunked prefill.

    ``block_size`` defaults to the autotuner's cached ``paged_attention``
    pick when a tuner is attached (the tunable block-size axis), else 16.
    ``n_blocks`` defaults to the slot-equivalent pool
    (``max_batch x ceil(max_len/block_size)``); size it smaller to serve
    the same traffic in strictly less KV memory — preemption-by-eviction
    keeps the engine correct when the pool runs dry.
    """

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_len: int = 512, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None, chunk_size: int = 32,
                 cost_model: Optional[CostModel] = None,
                 step_budget_s: Optional[float] = None,
                 autotuner=None, clock=None, compact_on_retire: bool = True,
                 fused: bool = True, telemetry=None, mesh=None):
        if model.init_paged_cache is None:
            raise NotImplementedError(
                f"{model.cfg.name}: no paged KV cache for this architecture")
        if mesh is not None and not fused:
            raise ValueError("a sharded replica (mesh=...) requires the "
                             "fused decode path (fused=True); the legacy "
                             "blocking path is single-device by design")
        self.model = model
        self.params = params
        # -- the sharded replica (mesh) ------------------------------------
        # One replica spanning plan.data x plan.model chips: the paged KV
        # pool is laid out with KV heads over 'model' and the [B] decode
        # loop state with batch rows over 'data'
        # (sharding.plans.paged_decode_shardings); block tables stay
        # replicated, so the host-side allocator / eviction / compaction
        # bookkeeping is identical to the single-device engine.  The fused
        # step closures are jitted with explicit in/out shardings — GSPMD
        # partitions the step, donation carries through unchanged (in ==
        # out sharding for the pool), and the [2, B] io echo stays the only
        # device->host sync — so the one-sync-per-step and donation
        # invariants hold verbatim on a mesh.
        self.mesh = mesh
        self._shardings = None
        self.sharding_log: List[str] = []
        if mesh is not None:
            from repro.sharding.plans import (named_tree,
                                              paged_decode_shardings,
                                              sanitize_specs, strip_axis)
            self._shardings = paged_decode_shardings(
                model.cfg, mesh, max_batch, self.sharding_log)
            pshapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
            # params: TP over 'model' only — 'data' stays replicated
            # (strip_axis documents why FSDP-split weights would break
            # the byte-identical-tokens contract)
            pspecs = sanitize_specs(strip_axis(model.param_specs()),
                                    pshapes, mesh, self.sharding_log)
            self._param_sh = named_tree(mesh, pspecs)
            self.params = jax.device_put(params, self._param_sh)
        self.max_batch = max_batch
        self.max_len = max_len
        self.cost_model = cost_model
        self.step_budget_s = step_budget_s
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.bind(self)
        self.autotuner = autotuner
        self._clock = clock if clock is not None else _time
        self.compact_on_retire = compact_on_retire
        self.fused = fused

        # the tuning cache resolves both paged axes here: block_size is a
        # cache-LAYOUT parameter (fixed at pool construction), while
        # num_splits is a launch parameter the kernel re-resolves at
        # dispatch (attention passes tuned=True) — kernel_splits records
        # the resolved value for telemetry either way
        self.kernel_splits = 1
        tuned_cfg = None
        if autotuner is not None:
            cfg = model.cfg
            shapes = {"batch": max_batch, "heads": cfg.n_heads,
                      "kv_heads": cfg.n_kv_heads,
                      "head_dim": cfg.head_dim, "ctx": max_len}
            tuned_cfg = autotuner.config_for("paged_attention", shapes)
            self.kernel_splits = int(tuned_cfg.get("num_splits", 1))
        if block_size is None:
            block_size = (int(tuned_cfg["block_size"])
                          if tuned_cfg is not None else 16)
        self.block_size = block_size
        self.max_blocks_per_seq = blocks_for_tokens(max_len, block_size)
        if n_blocks is None:
            n_blocks = max_batch * self.max_blocks_per_seq
        if n_blocks < self.max_blocks_per_seq:
            # one sequence must always be able to reach max_len, or the
            # oldest-request progress guarantee (and so termination) breaks
            raise ValueError(
                f"n_blocks={n_blocks} < blocks for one max_len sequence "
                f"({self.max_blocks_per_seq})")
        self.n_blocks = n_blocks

        self.allocator = BlockAllocator(n_blocks, block_size)
        self.scheduler = ChunkedPrefillScheduler(
            chunk_size, step_budget_s=step_budget_s)
        self.chunk_size = chunk_size
        if mesh is not None:
            self.cache = model.init_paged_cache(n_blocks, block_size,
                                                mesh=mesh)
        else:
            self.cache = model.init_paged_cache(n_blocks, block_size)
        self.block_tables = np.full(
            (max_batch, self.max_blocks_per_seq), -1, np.int32)
        self._bt_dev = None             # cached device copy of block_tables
        self.rows: List[Optional[_Row]] = [None] * max_batch
        self.done: Dict[int, Request] = {}
        self.stats = EngineStats()
        self._rid = itertools.count()
        self._pred_cache: Dict = {}
        self._decode_text: Optional[str] = None
        self._pending = None
        # expert counts of the launches since the last decode dispatch,
        # on the device until the decode's echo is synced
        self._launch_counts: List[dict] = []
        self._drained_counts: Dict[str, int] = {}
        step_fn = _decode_step_fn(model)
        if fused:
            self._toks = self._dev(np.zeros(max_batch, np.int32), "batch")
            fused_decode = paged_decode_fn(step_fn, mesh)
            fused_chunk = paged_chunk_fn(step_fn, mesh)

            if mesh is None:
                self._decode = jax.jit(fused_decode, donate_argnums=(1,))
                self._chunk = jax.jit(fused_chunk, donate_argnums=(1, 5))
            else:
                # explicit in/out shardings: GSPMD partitions the step, and
                # — critically — they survive ``.lower().compile()``, so the
                # AOT executable ``_predict_decode`` swaps in keeps the
                # exact same layout contract as the jitted path.  The pool
                # keeps one sharding on both sides of the step, so donation
                # is an in-place per-shard update, never a reshard.
                sh = self._shardings
                pool_sh = jax.tree.map(lambda _: sh["pool"], self.cache)
                self._pool_sh = pool_sh
                self._decode = jax.jit(
                    fused_decode, donate_argnums=(1,),
                    in_shardings=(self._param_sh, pool_sh, sh["batch"],
                                  sh["batch"], sh["repl"]),
                    out_shardings=(sh["io"], sh["batch"], pool_sh,
                                   sh["repl"]))
                self._chunk = jax.jit(
                    fused_chunk, donate_argnums=(1, 5),
                    in_shardings=(self._param_sh, pool_sh, sh["repl"],
                                  sh["repl"], sh["repl"], sh["batch"],
                                  sh["repl"], sh["repl"]),
                    out_shardings=(pool_sh, sh["batch"], sh["repl"]))
        else:
            self._decode = jax.jit(model.decode)     # batch decode [B, 1]
            self._chunk = jax.jit(model.decode)      # chunk prefill [1, C]

    # -- public ---------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               submitted_s: Optional[float] = None) -> int:
        """Enqueue one request.  ``submitted_s`` is the external-admission
        hook (see the slot engine's ``submit``): a cluster re-route keeps
        the request's original arrival time for latency accounting."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) >= self.max_len:
            # over-long prompts must be rejected HERE: mid-trace they
            # would grow past the fixed-width block table and strand a
            # freshly-allocated block outside any table (a pool leak)
            raise ValueError(f"prompt of {len(prompt)} tokens cannot fit "
                             f"max_len={self.max_len} (needs >= 1 decode "
                             "slot)")
        rid = next(self._rid)
        self.scheduler.submit(Request(rid, prompt, max_new_tokens, eos_id,
                                      submitted_s=self._clock.time()
                                      if submitted_s is None else submitted_s))
        return rid

    @property
    def queue(self):
        return self.scheduler.queue

    def kv_cache_bytes(self) -> int:
        """Resident bytes of the paged KV store: ``n_blocks x block_size``
        token slots regardless of ``max_batch x max_len``.  Fused steps
        donate the pool, so this is the peak too."""
        return int(sum(x.nbytes for x in jax.tree.leaves(self.cache)))

    # -- cost-model pricing ---------------------------------------------------
    def _predict_decode(self) -> Prediction:
        """Price the paged decode step; like the slot engine, the AOT
        executable replaces the jitted decode (shapes never change) and
        keeps the jit path's pool donation.  The compiled HLO text is
        kept (``_decode_text``) so recalibration can re-price without
        re-lowering (see the slot engine's ``_predict_decode``)."""
        key = ("decode", self.max_batch)
        if key not in self._pred_cache:
            if self._decode_text is None:
                pos = jnp.zeros((self.max_batch,), jnp.int32)
                bt = jnp.full((self.max_batch, self.max_blocks_per_seq), -1,
                              jnp.int32)
                if self.fused:
                    toks = jnp.zeros((self.max_batch,), jnp.int32)
                else:
                    toks = jnp.zeros((self.max_batch, 1), jnp.int32)
                compiled = self._decode.lower(self.params, self.cache, toks,
                                              pos, bt).compile()
                self._decode_text = compiled.as_text()
                self._decode = compiled
            self._pred_cache[key] = self.cost_model.predict_compiled(
                self._decode_text)
        return self._pred_cache[key]

    def _predict_chunk(self) -> Prediction:
        """Price one prefill chunk as a chunk_size-token prefill (chunks
        never shrink: final partial chunks overlap).

        APPROXIMATION: the analytic census is parameter-streaming
        dominated and linear in tokens — it does not model attention over
        the row's already-filled context, for chunks here exactly as for
        whole prompts in the slot engine's ``_predict_prefill``.  Late
        chunks of a long prompt therefore cost somewhat more than this
        gate charges them; the budget bounds chunk COUNT per step
        faithfully, not long-context attention."""
        key = ("chunk", self.chunk_size)
        if key not in self._pred_cache:
            self._pred_cache[key] = _analytic_prefill_prediction(
                self.cost_model, self.model.cfg, self.chunk_size)
        return self._pred_cache[key]

    # -- block management -----------------------------------------------------
    def _retirement_bound(self, row: _Row) -> bool:
        """True when the row cannot legitimately decode again — its
        retirement is already in the pending drain, so any further
        dispatch is a pure shadow step.  Two host-computable cases: a
        prior dispatch reached the cache-ceiling retire point
        (pos_after >= max_len-1; a fresh prefill AT max_len-1 still owes
        its one decode), or every token the budget allows is already
        dispatched (delivered length after D drained dispatches is D+1;
        retire at >= max_new, with the legacy floor of one decode).
        Only eos retirements, which need the synced token, are not
        predictable here."""
        if row.dispatched > 0 and row.pos >= self.max_len - 1:
            return True
        return row.dispatched >= max(row.req.max_new_tokens - 1, 1)

    def _dev(self, x, kind: str = "repl"):
        """THE host->device boundary for per-step operands.  Unsharded:
        a plain uncommitted upload (``jnp.asarray``), exactly the old
        behavior.  Sharded: an explicit ``jax.device_put`` onto the
        replica mesh with the named sharding — required because the AOT
        decode executable (``_predict_decode``) checks operand shardings
        instead of auto-resharding, and because an uncommitted
        single-device array would not even live on the mesh's device
        set."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._shardings[kind])

    def _bt_device(self):
        """The device block tables, uploaded only when a table row
        actually mutated (growth, eviction, retire, compaction) instead
        of fresh per step.  Replicated on a mesh: every shard reads the
        whole table to translate logical slots to physical blocks."""
        if self._bt_dev is None:
            self._bt_dev = self._dev(self.block_tables)
            self.stats.table_uploads += 1
        return self._bt_dev

    def _row_blocks(self, idx: int) -> List[int]:
        return [int(b) for b in self.block_tables[idx] if b >= 0]

    def _free_row(self, idx: int) -> None:
        self.allocator.free(self._row_blocks(idx))
        self.block_tables[idx] = -1
        self._bt_dev = None
        self.rows[idx] = None

    def _placed(self) -> List[int]:
        return [i for i, r in enumerate(self.rows) if r is not None]

    def _evict_for(self, needy: int) -> bool:
        """Free blocks by evicting a victim row.  Victim: the YOUNGEST
        placed request, excluding the needy row itself and the OLDEST
        placed request (never evicted — that guarantee makes the engine
        terminate: the oldest always keeps its blocks, completes, and
        frees them).  Returns False when no eligible victim exists."""
        placed = self._placed()
        oldest = min(placed, key=lambda i: self.rows[i].req.rid)
        cands = [i for i in placed if i != needy and i != oldest]
        if not cands:
            return False
        victim = max(cands, key=lambda i: self.rows[i].req.rid)
        row = self.rows[victim]
        req = row.req
        self._free_row(victim)
        # the victim replays from scratch: roll back its DELIVERED-token
        # accounting so replayed tokens are not double-counted (the
        # paged_serve throughput comparison reads decoded_tokens).
        # prefill_chunks/preemptions stay — they record work actually
        # done.  ``row.ready`` (not ``req.tokens``) keys the rollback:
        # on the fused path a ready row's first token may still be in
        # flight (the echo), leaving the list briefly empty.
        if row.ready:
            self.stats.decoded_tokens -= max(len(req.tokens) - 1, 0)
            self.stats.prefills -= 1
        req.tokens.clear()           # replayed from scratch on re-admission
        self.scheduler.requeue(req)
        self.stats.preemptions += 1
        return True

    def _ensure_blocks(self, idx: int, n_needed: int) -> bool:
        """Grow row ``idx``'s block table to ``n_needed`` blocks, evicting
        if the pool is dry.  Returns False when the row must wait."""
        if n_needed > self.max_blocks_per_seq:
            # unreachable given the submit() length check + the
            # max_len - 1 retire cap, but fail loudly BEFORE allocating:
            # a block granted past the table width belongs to no table
            # and would leak
            raise AssertionError(
                f"row {idx} needs {n_needed} blocks > table width "
                f"{self.max_blocks_per_seq}")
        bt = self.block_tables[idx]
        have = int((bt >= 0).sum())
        while have < n_needed:
            b = self.allocator.alloc()
            if b is None:
                if not self._evict_for(idx):
                    return False
                continue
            bt[have] = b
            have += 1
            self._bt_dev = None      # table row mutated
        return True

    def _maybe_compact(self) -> None:
        """Copy-on-retire compaction: densify the allocated blocks so the
        touched span of the pool stays minimal.  One functional
        gather-then-scatter per cache leaf, so overlapping moves are safe."""
        if not self.compact_on_retire:
            return
        plan = self.allocator.compaction_plan()
        if plan is None:
            return
        src, dst = plan
        s = self._dev(np.asarray(src, np.int32))
        d = self._dev(np.asarray(dst, np.int32))
        self.cache = jax.tree.map(
            lambda c: c.at[:, d].set(c[:, s]), self.cache)
        if self.mesh is not None:
            # the block axis (1) is unsharded, so the copy is shard-local;
            # re-pin the result in case eager sharding propagation picked
            # a different layout — the AOT decode executable checks
            # operand shardings instead of auto-resharding
            self.cache = jax.device_put(self.cache, self._pool_sh)
        for i in self._placed():
            self.block_tables[i] = remap_table(
                list(self.block_tables[i]), src, dst)
        self._bt_dev = None
        self.allocator.commit_compaction()
        self.stats.compactions += 1

    # -- prefill chunks -------------------------------------------------------
    def _place(self, req: Request) -> Optional[int]:
        free = [i for i, r in enumerate(self.rows) if r is None]
        if not free:
            return None
        idx = free[0]
        self.rows[idx] = _Row(req)
        self.scheduler.take(req)
        self.stats.admission_order.append(req.rid)
        return idx

    @spans.traced(spans.CHUNK)
    def _run_chunk(self, idx: int) -> None:
        """Advance row ``idx``'s prefill by one chunk.

        Chunks are always exactly ``chunk_size`` tokens so the jitted call
        never retraces: the final chunk of a prompt *overlaps* already-
        written positions (re-running the same tokens against the same
        cache rewrites identical K/V — chunked prefill is deterministic),
        and prompts shorter than one chunk are LEFT-padded with the write
        positions pushed negative, which the paged scatter drops.

        Fused: the pool is donated, and the final chunk's first-token
        argmax lands in the device token array (no host transfer — the
        value reaches ``req.tokens`` via the first decode's echo)."""
        row = self.rows[idx]
        req, C = row.req, self.chunk_size
        S = len(req.prompt)
        end = min(row.filled + C, S)
        start = end - C              # < filled on overlap, < 0 on left-pad
        if not self._ensure_blocks(idx, blocks_for_tokens(end,
                                                          self.block_size)):
            return                   # pool dry, no victim: retry next step
        if self.rows[idx] is not row:
            return                   # the eviction chain took this row
        toks = np.zeros(C, np.int32)
        lo = max(start, 0)
        toks[C - (end - lo):] = req.prompt[lo:end]
        with TraceAnnotation(spans.UPLOAD):
            bt = self._bt_device()
            head = (self._dev(toks[None]),
                    self._dev(np.asarray([start], np.int32)))
            if self.fused:
                tail = (self._dev(np.int32(idx)), self._dev(end == S))
        with TraceAnnotation(spans.LAUNCH):
            bt = bt[idx:idx + 1]      # a program of its own on the device
            if self.fused:
                self.cache, self._toks, counts = self._chunk(
                    self.params, self.cache, *head, bt, self._toks, *tail)
                if counts:
                    self._launch_counts.append(counts)
            else:
                logits, self.cache = self._chunk(
                    self.params, self.cache, *head, bt)
        row.filled = end
        self.stats.prefill_chunks += 1
        if end == S:
            row.ready = True
            row.pos = S
            self.stats.prefills += 1
            if not self.fused:
                row.last_tok = int(self._sync(jnp.argmax(logits[0])))
                self._book_first_token(req, row.last_tok)

    # -- the engine iteration -------------------------------------------------
    def _step(self) -> int:
        """One iteration inside the span ``serve.step``, which ends
        with the iteration's counts (``telemetry.spans``).  Returns the
        number of placed rows (>= 1 while a step is still in flight).
        (``step()`` is the inherited autotuner-installing shell.)"""
        with TraceAnnotation(spans.STEP) as span:
            n, rows = self._iterate()
            if span.is_enabled():
                span.set_metadata(rows=rows, **self._drained_counts)
        return n

    def _iterate(self) -> "tuple[int, int]":
        """Plan, run prefill chunks, dispatch the decode, then drain
        the PREVIOUS step (fused) — so step N's tokens are synced only
        after step N+1 is on the device, and retire/admit/schedule
        bookkeeping runs in the device step's shadow.  Returns ``_step``'s
        row count and the rows the decode stepped."""
        t0, self._sync_s = self._clock.perf_counter(), 0.0
        prev, self._pending = self._pending, None
        self._drained_counts = {}
        unfinished = sorted(
            ((i, self.rows[i].req.rid, self.rows[i].req)
             for i in self._placed() if not self.rows[i].ready),
            key=lambda t: t[1])
        n_free = self.rows.count(None)
        any_ready = any(r is not None and r.ready for r in self.rows)
        if not unfinished and not any_ready and not self.scheduler.queue:
            self._drain(prev)        # flush the tail step, if any
            return 0, 0
        chunks_before = self.stats.prefill_chunks
        with TraceAnnotation(spans.PLAN):
            budget = self._step_budget()
            gated = self.cost_model is not None and budget is not None
            decode_s = self._predict_decode().step_s \
                if self.cost_model is not None else 0.0
            chunk_s = self._predict_chunk().step_s \
                if self.cost_model is not None else 0.0
            plan = self.scheduler.plan(
                unfinished=unfinished, n_free_rows=n_free,
                any_ready=any_ready, decode_s=decode_s, chunk_s=chunk_s,
                gated=gated, budget_s=budget)
        self.stats.deferred_prefills += plan.deferred

        for item in plan.items:
            if item.row is None:
                idx = self._place(item.request)
                if idx is None:      # an eviction refilled the rows
                    continue
            else:
                idx = item.row
                if (self.rows[idx] is None
                        or self.rows[idx].req.rid != item.rid):
                    continue         # evicted mid-step; replanned later
            self._run_chunk(idx)

        active = self._decode_phase()

        # the allocator records the exact intra-step peak (a row can grow
        # a block AND retire within one _decode_phase; sampling n_in_use
        # here would miss that high-water mark)
        self.stats.peak_blocks_in_use = self.allocator.peak_in_use
        # an iteration can dispatch nothing when its only ready rows are
        # retirement-bound in the pending drain: it does not count
        did_work = bool(plan.items) or active
        self._drain(prev)
        chunks = self.stats.prefill_chunks - chunks_before
        if did_work:
            self.stats.steps += 1
            measured = self._clock.perf_counter() - t0
            if self.telemetry is not None:
                self.telemetry.on_step(self._step_record(
                    plan.predicted_s, measured, active, chunks, budget))
        n = len(self._placed())
        return (n if self._pending is None else max(n, 1)), active

    def _step_record(self, planned: float, measured: float,
                     n_decoded_rows: int, n_chunks: int,
                     budget: Optional[float]):
        """One telemetry ``StepRecord`` for this iteration.
        ``n_prefill_units`` counts chunks actually RUN (a planned chunk
        can be skipped when the pool is dry), so drift attribution sees
        the work the measured latency paid for."""
        from repro.serve.telemetry.metrics import StepRecord
        pred = self._pred_cache.get(("decode", self.max_batch))
        return StepRecord(
            engine="paged", step=self.stats.steps, t_s=self._clock.time(),
            n_active=len(self._placed()),
            queue_depth=len(self.scheduler.queue),
            predicted_s=planned,
            predicted_decode_s=pred.step_s if pred else 0.0,
            measured_s=measured, decode_ran=n_decoded_rows > 0,
            n_prefill_units=n_chunks,
            bottleneck=getattr(pred, "bottleneck", ""),
            budget_s=budget if budget is not None else 0.0,
            host_syncs=self.stats.host_syncs,
            table_uploads=self.stats.table_uploads,
            blocks_in_use=self.allocator.n_in_use, n_blocks=self.n_blocks,
            decoded_tokens=self.stats.decoded_tokens,
            preemptions=self.stats.preemptions,
            deferred=self.stats.deferred_prefills,
            kernel_splits=self.kernel_splits,
            integrity_failures=self.stats.integrity_failures,
            sync_s=self._sync_s)

    @spans.traced(spans.DECODE)
    def _decode_phase(self) -> int:
        """Batched decode over the ready rows; rows mid-prefill (or whose
        block growth must wait) ride along masked out via write_pos=-1."""
        ready = [i for i in self._placed() if self.rows[i].ready]
        if not ready:
            return 0
        stepping = []
        for i in ready:
            row = self.rows[i]
            if row is None or not row.ready:
                continue             # evicted by an earlier row's growth
            if self.fused and self._retirement_bound(row):
                # pipelining: the row's retirement is already determined
                # by host-visible state (cache ceiling / token budget) and
                # sits in the pending drain — a further shadow dispatch
                # would only burn a step and could grow a block (even
                # evicting a LIVE victim) for output the drain drops.
                # Only eos retirements, which need the synced token,
                # still cost one shadow step.
                continue
            need = blocks_for_tokens(row.pos + 1, self.block_size)
            if self._ensure_blocks(i, need) and self.rows[i] is row:
                stepping.append((i, row))
        # a LATER row's block growth may have evicted a row already
        # collected above — re-validate the whole list before stepping
        stepping = [(i, row) for i, row in stepping if self.rows[i] is row]
        if not stepping:
            return 0
        pos = np.full(self.max_batch, -1, np.int32)
        for i, row in stepping:
            pos[i] = row.pos
        if self.fused:
            with TraceAnnotation(spans.UPLOAD):
                pos_d, bt = self._dev(pos, "batch"), self._bt_device()
            with TraceAnnotation(spans.LAUNCH):
                io, self._toks, self.cache, counts = self._decode(
                    self.params, self.cache, self._toks, pos_d, bt)
            if counts:
                self._launch_counts.append(counts)
            # the snapshot carries each row's post-step position: that is
            # the value retire checks compare against at drain time
            # (row.pos itself may advance again before the drain); the
            # launches' expert counts ride along to the same sync
            self._pending = (io, [(i, row, row.pos + 1)
                                  for i, row in stepping],
                             self._launch_counts)
            self._launch_counts = []
            for i, row in stepping:
                row.pos += 1
                row.dispatched += 1
            return len(stepping)
        toks = np.zeros((self.max_batch, 1), np.int32)
        for i, row in stepping:
            toks[i, 0] = row.last_tok
        with TraceAnnotation(spans.UPLOAD):
            args = (jnp.asarray(toks), jnp.asarray(pos), self._bt_device())
        with TraceAnnotation(spans.LAUNCH):
            logits, self.cache = self._decode(self.params, self.cache, *args)
        nxt = self._sync(jnp.argmax(logits, axis=-1)).astype(np.int32)
        for i, row in stepping:
            req = row.req
            req.tokens.append(int(nxt[i]))
            self.stats.decoded_tokens += 1
            row.last_tok = int(nxt[i])
            row.pos += 1
            hit_eos = req.eos_id is not None and nxt[i] == req.eos_id
            out_of_budget = len(req.tokens) >= req.max_new_tokens
            out_of_cache = row.pos >= self.max_len - 1
            if hit_eos or out_of_budget or out_of_cache:
                self._retire(i)
        return len(stepping)

    def _drain(self, pending) -> None:
        """Sync and book one in-flight fused step (see the slot engine's
        ``_drain``); rows evicted or retired since dispatch are dropped
        by identity, so replays and shadow steps never double-count."""
        if pending is None:
            return
        io, snap, launches = pending
        arr, launches = self._sync((io, launches))
        self._book_expert_counts(launches)
        if not _echo_ok(arr):
            self.stats.integrity_failures += 1   # see the slot _drain
            return
        in_t, out_t = arr[0], arr[1]
        for i, row, pos_after in snap:
            if self.rows[i] is not row:
                continue
            req = row.req
            if not req.tokens:
                self._book_first_token(req, int(in_t[i]))  # echoed prefill
            req.tokens.append(int(out_t[i]))
            self.stats.decoded_tokens += 1
            hit_eos = req.eos_id is not None and req.tokens[-1] == req.eos_id
            out_of_budget = len(req.tokens) >= req.max_new_tokens
            out_of_cache = pos_after >= self.max_len - 1
            if hit_eos or out_of_budget or out_of_cache:
                self._retire(i)

    def _book_expert_counts(self, launches: List[dict]) -> None:
        """Add the synced launches' expert counts to ``stats``; the step
        span (``serve.step``) carries their sum and maximum."""
        if not launches:
            return
        pairs = sum(int(c[EXPERT_PAIRS]) for c in launches)
        most = max(int(c[EXPERT_MAX]) for c in launches)
        self.stats.expert_pairs += pairs
        self.stats.expert_max_load = max(self.stats.expert_max_load, most)
        self._drained_counts = {EXPERT_PAIRS: pairs, EXPERT_MAX: most}

    @spans.traced(spans.RETIRE)
    def _retire(self, idx: int) -> None:
        req = self.rows[idx].req
        req.finished_s = self._clock.time()
        self.done[req.rid] = req
        self._free_row(idx)
        self.stats.completed += 1
        if self.telemetry is not None:
            self.telemetry.on_retire(req)
        self._maybe_compact()

    def run_until_done(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            active = self.step()
            if active == 0 and not self.scheduler.queue:
                break
        if self._pending is not None:        # max_steps exhausted mid-flight
            self._drain(self._pending)
            self._pending = None
        self.allocator.check()
        return self.stats
