"""The named-experiment registry: one entry per paper campaign.

Each experiment maps a published table of the paper (Abdelkhalik et al.,
arXiv:2208.11174) onto this backend's measurement primitives:

  * ``alu_chain``            - Tables I/II: per-op latency via chain-length
                               regression, dependent vs independent
  * ``memory_chase``         - Table IV / Fig. 2-3: pointer-chase walk of the
                               memory hierarchy + streaming bandwidth
  * ``mxu_shapes``           - Table III: matrix-unit latency/throughput per
                               dtype x tile shape (the WMMA fragment sweep)
  * ``roofline_calibration`` - achieved peaks (MXU TFLOP/s, HBM GB/s,
                               dispatch overhead) that anchor the perf model
  * ``isa_mapping``          - Table V: source -> optimized instruction
                               expansion per op class (the PTX->SASS map)
  * ``autotune``             - the tables applied: cost-model-guided launch
                               configs per tunable kernel (predicted best
                               vs default, optional measured refinement)
  * ``paged_serve``          - the memory model applied to serving: slot vs
                               paged KV cache on the same request trace
                               (tokens/s, resident KV bytes, preemptions)
  * ``decode_hotpath``       - the transfer/donation model applied to the
                               decode loop: legacy blocking path vs the
                               fused one (on-device sampling, donated
                               caches, pipelined steps) on the same trace
  * ``telemetry_replay``     - the model watched in production: the drift
                               -> recalibration and SLO-overload scenarios
                               replayed on the deterministic sim harness
  * ``traffic_scaling``      - the model placing traffic: offered load x
                               replica count through the cluster router,
                               round-robin vs cost-aware placement
                               (tok/s, p50/p99, shed rate, conservation)
  * ``chaos_serving``        - the cluster under injected faults: crash /
                               hang / corrupt / crash-loop x replica
                               count, gating byte-identical survivors,
                               zero lost tokens, zero leaked blocks and
                               restart-budget quarantine

Cell runners take ``(params, quick=...)`` and return a flat-ish metrics
dict; the scheduler in ``runner.py`` owns ordering, persistence and resume.
"""
from __future__ import annotations

from typing import Any, Dict, List

from repro.core.campaign.spec import Experiment

# ---------------------------------------------------------------------------
# cell runners (one grid point each; heavy imports stay inside the calls so
# `campaign list` and the result/report tooling never pay jax startup twice)
# ---------------------------------------------------------------------------


def run_alu_cell(params: Dict[str, Any], quick: bool = False) -> Dict[str, Any]:
    import jax.numpy as jnp
    from repro.core.microbench import harness

    lengths = (4, 16, 64) if quick else (4, 16, 64, 256)
    r = harness.run_chain(harness.OPS[params["op"]], params["op"],
                          dtype=jnp.dtype(params["dtype"]), lengths=lengths,
                          dependent=params["dependent"])
    return {
        "per_op_ns": r.per_op_s * 1e9,
        "overhead_ns": r.overhead_s * 1e9,
        "lengths": list(r.lengths),
        "times_us": [t * 1e6 for t in r.times_s],
        "cpi_curve": {str(k): v for k, v in r.cpi_curve.items()},
    }


def run_chase_cell(params: Dict[str, Any], quick: bool = False
                   ) -> Dict[str, Any]:
    from repro.core.microbench import memory

    size_bytes = params["size_kib"] * 1024
    if params.get("access", "chase") == "stream":
        bw = memory.streaming_bandwidth(size_bytes)
        return {"gbps": bw / 1e9, "working_set_bytes": size_bytes}
    hops = (64, 256, 1024) if quick else (256, 1024, 4096)
    r = memory.run_chase(size_bytes, hop_counts=hops)
    return {
        "per_hop_ns": r.per_hop_s * 1e9,
        "overhead_ns": r.overhead_s * 1e9,
        "working_set_bytes": r.working_set_bytes,
        "hops": list(r.hops),
        "times_us": [t * 1e6 for t in r.times_s],
    }


def run_mxu_cell(params: Dict[str, Any], quick: bool = False
                 ) -> Dict[str, Any]:
    from repro.core.microbench import mxu

    lengths = (1, 2, 4) if quick else (1, 2, 4, 8)
    # no s8 dot on this harness's backends: int8 cells measure the bf16
    # path (the old table3 behaviour) and record the substitution
    dtype = params["dtype"]
    compute_dtype = "bfloat16" if dtype == "int8" else dtype
    r = mxu.run_mxu(dtype=compute_dtype, shape=tuple(params["shape"]),
                    dependent=params["dependent"], lengths=lengths)
    return {
        "per_op_us": r.per_op_s * 1e6,
        "overhead_us": r.overhead_s * 1e6,
        "flops": r.flops,
        "tflops": r.tflops,
        "compute_dtype": compute_dtype,
    }


def run_roofline_cal_cell(params: Dict[str, Any], quick: bool = False
                          ) -> Dict[str, Any]:
    """Measure one achieved-peak term of the roofline on this backend."""
    term = params["term"]
    if term == "mxu_peak_tflops":
        from repro.core.microbench import mxu
        shape = (256, 256, 256) if quick else (512, 512, 512)
        r = mxu.run_mxu(dtype="float32", shape=shape, dependent=False,
                        lengths=(1, 2, 4))
        return {"value": r.tflops, "unit": "TFLOP/s",
                "detail": f"independent f32 matmul {shape}"}
    if term == "hbm_stream_gbs":
        from repro.core.microbench import memory
        size = 16 * 2**20 if quick else 64 * 2**20
        bw = memory.streaming_bandwidth(size)
        return {"value": bw / 1e9, "unit": "GB/s",
                "detail": f"sequential reduce over {size // 2**20} MiB"}
    if term == "dispatch_overhead_us":
        import jax.numpy as jnp
        from repro.core.microbench import harness
        r = harness.run_chain(harness.OPS["add"], "add", dtype=jnp.float32,
                              lengths=(1, 2, 4, 8), dependent=True)
        return {"value": r.overhead_s * 1e6, "unit": "us",
                "detail": "t(K)=a+bK regression intercept, add.f32"}
    raise ValueError(f"unknown roofline calibration term {term!r}")


def run_isa_cell(params: Dict[str, Any], quick: bool = False
                 ) -> Dict[str, Any]:
    """StableHLO -> optimized-HLO expansion for one op class (Table V)."""
    import jax
    import jax.numpy as jnp
    from repro.core.isa import hlo_census as hc

    cases = {
        "add.f32": lambda x: x + 1.0,
        "mul.f32": lambda x: x * 1.5,
        "fma.f32": lambda x: x * 1.5 + 2.0,
        "div.f32": lambda x: x / 1.5,
        "rsqrt.f32": lambda x: jax.lax.rsqrt(jnp.abs(x) + 1e-3),
        "exp.f32": lambda x: jnp.exp(x * 1e-3),
        "tanh.f32": lambda x: jnp.tanh(x),
        "softmax.f32": lambda x: jax.nn.softmax(x, axis=-1),
        "matmul.f32": lambda x: x @ x.T,
        "reduce.f32": lambda x: jnp.sum(x, axis=-1),
        "gather": lambda x: x[jnp.arange(8) % x.shape[0]],
        "scan8": lambda x: jax.lax.scan(lambda c, _: (c * 1.01, ()), x,
                                        None, length=8)[0],
    }
    fn = cases[params["case"]]
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    lowered = jax.jit(fn).lower(x)
    compiled = lowered.compile()
    m = hc.op_mapping_table(lowered.as_text(), compiled.as_text())
    c = hc.census(compiled.as_text())
    top = {k: int(v) for k, v in list(c["op_histogram"].items())[:3]}
    return {
        "n_source_ops": m["n_source_ops"],
        "n_optimized_ops": m["n_optimized_ops"],
        "flops": int(c["flops"]),
        "top_ops": top,
    }


ISA_CASES = ("add.f32", "mul.f32", "fma.f32", "div.f32", "rsqrt.f32",
             "exp.f32", "tanh.f32", "softmax.f32", "matmul.f32",
             "reduce.f32", "gather", "scan8")


def run_autotune_cell(params: Dict[str, Any], quick: bool = False
                      ) -> Dict[str, Any]:
    """Tune one kernel's launch space: analytic ranking always (pure cost
    model, runs on CPU), measured top-K refinement when mode='measured'
    (interpret-mode kernels off-TPU — slow but true wall time)."""
    from repro.core.autotune import Autotuner
    from repro.core.costmodel import CostModel

    measured = params.get("mode", "analytic") == "measured"
    tuner = Autotuner(CostModel.from_named(params.get("calibration",
                                                      "tpu_v5e")),
                      measure=measured, top_k=2 if quick else 3)
    shapes = None
    if quick or measured:
        # small problems keep interpret-mode timing (and CI) tractable
        shapes = {
            "flash_attention": {"batch": 1, "seq_q": 128, "seq_kv": 128,
                                "heads": 2, "kv_heads": 1, "head_dim": 64},
            "paged_attention": {"batch": 2, "heads": 2, "kv_heads": 1,
                                "head_dim": 32, "ctx": 128},
            "ssm_scan": {"batch": 1, "seq": 64, "d_inner": 256,
                         "state_dim": 8},
            "wkv6": {"batch": 1, "seq": 64, "heads": 4, "head_dim": 32},
            "mxu_probe": {"m": 256, "k": 256, "n": 256},
        }[params["kernel"]]
    res = tuner.tune(params["kernel"], shapes, dtype=params["dtype"])
    out = {
        "best_config": dict(res.best),
        "default_config": dict(res.default),
        "predicted_best_s": res.predicted_best_s,
        "predicted_default_s": res.predicted_default_s,
        "predicted_speedup": res.predicted_speedup,
        "n_candidates": len(res.ranked),
        "cache_key": res.key,
    }
    if res.measured_best_s is not None:
        out["measured_best_s"] = res.measured_best_s
        if res.measured_speedup is not None:
            out["measured_speedup"] = res.measured_speedup
    return out


def run_paged_serve_cell(params: Dict[str, Any], quick: bool = False
                         ) -> Dict[str, Any]:
    """Serve one deterministic mixed-length trace through BOTH engines and
    compare: tokens/s, resident KV bytes, greedy-token equality, and the
    paged engine's preemption/leak accounting."""
    import time

    import jax
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.models.zoo import build_model
    from repro.serve import PagedServingEngine, ServingEngine

    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    weights = model.init(jax.random.PRNGKey(0))
    n_req = 6 if quick else int(params.get("n_requests", 16))
    max_batch, max_len = 4, 64
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(1, 33))).astype(np.int32)
               for _ in range(n_req)]

    slot = ServingEngine(model, weights, max_batch=max_batch,
                         max_len=max_len)
    rids_s = [slot.submit(p, max_new_tokens=6) for p in prompts]
    t0 = time.perf_counter()
    s_stats = slot.run_until_done()
    slot_s = time.perf_counter() - t0

    bs = int(params["block_size"])
    pool = params.get("n_blocks")
    # default pool: ~60% of the slot-equivalent rectangle — the memory
    # saving the paged layout exists to bank
    n_blocks = int(pool) if pool else max(
        -(-max_len // bs), int(0.6 * max_batch * (-(-max_len // bs))))
    paged = PagedServingEngine(model, weights, max_batch=max_batch,
                               max_len=max_len, block_size=bs,
                               n_blocks=n_blocks,
                               chunk_size=int(params.get("chunk", 16)))
    rids_p = [paged.submit(p, max_new_tokens=6) for p in prompts]
    t0 = time.perf_counter()
    p_stats = paged.run_until_done(max_steps=20_000)
    paged_s = time.perf_counter() - t0

    identical = all(slot.done[a].tokens == paged.done[b].tokens
                    for a, b in zip(rids_s, rids_p))
    paged.allocator.check()
    return {
        "completed_slot": s_stats.completed,
        "completed_paged": p_stats.completed,
        "slot_tok_per_s": s_stats.decoded_tokens / max(slot_s, 1e-9),
        "paged_tok_per_s": p_stats.decoded_tokens / max(paged_s, 1e-9),
        "slot_kv_bytes": slot.kv_cache_bytes(),
        "paged_kv_bytes": paged.kv_cache_bytes(),
        "kv_bytes_ratio": paged.kv_cache_bytes() / slot.kv_cache_bytes(),
        "identical_tokens": identical,
        "preemptions": p_stats.preemptions,
        "prefill_chunks": p_stats.prefill_chunks,
        "peak_block_occupancy": p_stats.peak_blocks_in_use / n_blocks,
        "blocks_leaked": n_blocks - paged.allocator.n_free,
    }


def run_decode_hotpath_cell(params: Dict[str, Any], quick: bool = False
                            ) -> Dict[str, Any]:
    """Serve one deterministic trace through an engine's legacy blocking
    path (``fused=False``: fresh uploads, [B, vocab] logits synced,
    undonated cache) and through the fused hot path (on-device sampling,
    donated caches, pipelined steps) and compare: tokens/s, host syncs
    per step, resident KV bytes, greedy-token equality, plus the analytic
    cost model's predicted per-step byte savings."""
    import time

    import jax
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.configs.base import ShapeCell
    from repro.core.costmodel import analytic
    from repro.models.zoo import build_model
    from repro.serve import PagedServingEngine, ServingEngine

    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    weights = model.init(jax.random.PRNGKey(0))
    n_req = 6 if quick else int(params.get("n_requests", 16))
    max_batch, max_len = 4, 64
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(1, 33))).astype(np.int32)
               for _ in range(n_req)]

    def build(fused):
        if params["engine"] == "paged":
            return PagedServingEngine(model, weights, max_batch=max_batch,
                                      max_len=max_len, block_size=8,
                                      chunk_size=16, fused=fused)
        return ServingEngine(model, weights, max_batch=max_batch,
                             max_len=max_len, fused=fused)

    out: Dict[str, Any] = {"engine": params["engine"]}
    done = {}
    warmup = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
              for _ in range(2)]
    for label, fused in (("baseline", False), ("fused", True)):
        eng = build(fused)
        # warm the engine first: each instance jits/AOT-compiles its own
        # step closures, and a cold timed region would mostly measure the
        # compiler (and charge the fused path for its extra jitted fns),
        # not steady-state decode — the thing this artifact tracks
        for p in warmup:
            eng.submit(p, max_new_tokens=4)
        eng.run_until_done(max_steps=20_000)
        steps0, dec0 = eng.stats.steps, eng.stats.decoded_tokens
        syncs0 = eng.stats.host_syncs
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        t0 = time.perf_counter()
        stats = eng.run_until_done(max_steps=20_000)
        wall = time.perf_counter() - t0
        done[label] = [eng.done[r].tokens for r in rids]
        steps = stats.steps - steps0
        out[f"{label}_tok_per_s"] = ((stats.decoded_tokens - dec0)
                                     / max(wall, 1e-9))
        out[f"{label}_steps"] = steps
        out[f"{label}_syncs_per_step"] = ((stats.host_syncs - syncs0)
                                          / max(steps, 1))
        out[f"{label}_kv_bytes"] = eng.kv_cache_bytes()
    out["identical_tokens"] = done["baseline"] == done["fused"]
    out["speedup"] = out["fused_tok_per_s"] / max(out["baseline_tok_per_s"],
                                                  1e-9)
    # the cost model's view of what the fused path removed per step
    cell = ShapeCell("hotpath", "decode", max_len, max_batch)
    legacy_b = analytic.analytic_serve_bytes(cfg, cell, 1, n_model=1)
    fused_b = analytic.analytic_serve_bytes(cfg, cell, 1, n_model=1,
                                            donated=True)
    out["predicted_hbm_bytes_saved"] = legacy_b - fused_b
    out["predicted_boundary_bytes_saved"] = (
        analytic.decode_boundary_bytes(cfg, cell)
        - analytic.decode_boundary_bytes(cfg, cell, device_sampling=True))
    return out


def run_telemetry_replay_cell(params: Dict[str, Any], quick: bool = False
                              ) -> Dict[str, Any]:
    """Replay one telemetry acceptance scenario on the deterministic sim
    harness (``repro.serve.sim``) and record its evidence dict: the
    drift scenario must show exactly one recalibration restoring the
    windowed prediction error under the 10% gate; the overload scenario
    must show the token bucket holding the p99 SLO that an ungated run
    of the same burst violates.  Both must keep tokens byte-identical."""
    from repro.serve.telemetry.scenarios import (run_drift_scenario,
                                                 run_overload_scenario)

    if params["scenario"] == "drift":
        res = run_drift_scenario(drift_factor=float(params.get("factor",
                                                               2.0)))
    else:
        res = run_overload_scenario(load_factor=int(params.get("load", 2)))
    # the per-event dicts are nested detail; the flat fields are the table
    res.pop("events", None)
    return res


# ---------------------------------------------------------------------------
# grids
# ---------------------------------------------------------------------------

# mirrors harness.OPS / INT_OPS / FLOAT_ONLY without importing jax at
# registry-import time; the constraint keeps the product paper-legal
_ALU_OPS = ("add", "sub", "mul", "fma", "max", "min", "abs", "and", "xor",
            "popc", "clz", "div", "rem", "rsqrt", "sqrt", "exp", "log",
            "sin", "tanh", "sigmoid", "select")
_INT_OPS = {"and", "xor", "popc", "clz"}
_FLOAT_ONLY = {"rsqrt", "sqrt", "exp", "log", "sin", "tanh", "sigmoid",
               "div", "fma"}


def _alu_legal(params: Dict[str, Any]) -> bool:
    is_int = params["dtype"].startswith("int")
    if is_int and params["op"] in _FLOAT_ONLY:
        return False
    if not is_int and params["op"] in _INT_OPS:
        return False
    return True


REGISTRY: Dict[str, Experiment] = {}


def register(exp: Experiment) -> Experiment:
    if exp.name in REGISTRY:
        raise ValueError(f"experiment {exp.name!r} already registered")
    REGISTRY[exp.name] = exp
    return exp


def get(name: str) -> Experiment:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown experiment {name!r}; available: "
                       f"{', '.join(names())}") from None


def names() -> List[str]:
    return sorted(REGISTRY)


register(Experiment(
    name="alu_chain",
    description="per-op latency via chain-length regression, dependent vs "
                "independent (paper Tables I/II)",
    grid={"op": _ALU_OPS,
          "dtype": ("float32", "bfloat16", "int32"),
          "dependent": (True, False)},
    quick_grid={"op": ("add", "mul", "fma", "exp"),
                "dtype": ("float32",),
                "dependent": (True, False)},
    constraint=_alu_legal,
    runner=run_alu_cell,
    cost_per_cell_s=2.0,
    tags=("vpu", "latency"),
))

register(Experiment(
    name="memory_chase",
    description="memory-hierarchy pointer chase + streaming bandwidth over "
                "working-set sizes (paper Table IV / Fig. 2-3)",
    grid={"access": ("chase", "stream"),
          "size_kib": (16, 256, 4096, 65536)},
    quick_grid={"access": ("chase", "stream"),
                "size_kib": (16, 4096)},
    runner=run_chase_cell,
    cost_per_cell_s=3.0,
    tags=("memory", "latency"),
))

register(Experiment(
    name="mxu_shapes",
    description="matrix-unit latency/throughput per dtype x tile shape "
                "(paper Table III, the WMMA fragment sweep; int8 measures "
                "the bf16 path where no s8 dot exists)",
    grid={"dtype": ("bfloat16", "float32", "int8"),
          "shape": ((128, 128, 128), (256, 256, 256), (512, 512, 128)),
          "dependent": (True, False)},
    quick_grid={"dtype": ("float32",),
                "shape": ((128, 128, 128),),
                "dependent": (True, False)},
    runner=run_mxu_cell,
    cost_per_cell_s=4.0,
    tags=("mxu", "throughput"),
))

register(Experiment(
    name="roofline_calibration",
    description="achieved peaks (MXU TFLOP/s, HBM GB/s, dispatch overhead) "
                "that anchor the roofline/predictor calibration",
    grid={"term": ("mxu_peak_tflops", "hbm_stream_gbs",
                   "dispatch_overhead_us")},
    runner=run_roofline_cal_cell,
    cost_per_cell_s=5.0,
    tags=("roofline", "calibration"),
))

register(Experiment(
    name="autotune",
    description="cost-model-guided kernel autotuning: ranked launch "
                "configs per tunable Pallas kernel (analytic; 'measured' "
                "adds the top-K wall-time refinement stage)",
    grid={"kernel": ("flash_attention", "paged_attention", "ssm_scan",
                     "wkv6", "mxu_probe"),
          "dtype": ("bf16",),
          "mode": ("analytic", "measured")},
    quick_grid={"kernel": ("flash_attention", "paged_attention", "ssm_scan",
                           "wkv6", "mxu_probe"),
                "dtype": ("bf16",),
                "mode": ("analytic",)},
    runner=run_autotune_cell,
    cost_per_cell_s=6.0,
    tags=("autotune", "costmodel"),
))

register(Experiment(
    name="paged_serve",
    description="slot vs paged KV-cache serving on one deterministic "
                "mixed-length trace: tokens/s, resident KV bytes, greedy "
                "equality, preemption + block-leak accounting",
    grid={"block_size": (8, 16), "chunk": (16,)},
    quick_grid={"block_size": (8,), "chunk": (8,)},
    runner=run_paged_serve_cell,
    cost_per_cell_s=30.0,
    tags=("serve", "paging", "memory"),
))

register(Experiment(
    name="decode_hotpath",
    description="legacy blocking decode vs the fused hot path (on-device "
                "sampling, donated caches, pipelined steps) on one trace: "
                "tok/s, host syncs/step, KV bytes, greedy equality",
    grid={"engine": ("slot", "paged")},
    runner=run_decode_hotpath_cell,
    cost_per_cell_s=30.0,
    tags=("serve", "hotpath", "memory"),
))

register(Experiment(
    name="telemetry_replay",
    description="production-telemetry scenarios on the sim harness: "
                "injected cost-model drift -> one online recalibration "
                "(error back under the 10% gate), and burst overload "
                "under the SLO token bucket (p99 held, newest shed)",
    grid={"scenario": ("drift", "overload")},
    runner=run_telemetry_replay_cell,
    cost_per_cell_s=20.0,
    tags=("serve", "telemetry", "costmodel"),
))

register(Experiment(
    name="isa_mapping",
    description="source -> optimized instruction expansion per op class "
                "(paper Table V, the PTX->SASS map)",
    grid={"case": ISA_CASES},
    quick_grid={"case": ("add.f32", "softmax.f32", "matmul.f32", "scan8")},
    runner=run_isa_cell,
    cost_per_cell_s=0.5,
    tags=("isa",),
))


def run_decode_longctx_cell(params: Dict[str, Any], quick: bool = False
                            ) -> Dict[str, Any]:
    """Split-KV flash-decoding sweep: one long-context decode-attention
    call at ``num_splits`` vs the unsplit kernel vs the jnp oracle.

    Interpret mode executes grid cells sequentially, so raw wall time
    cannot show a parallelism win on CPU CI.  The measured proxy models
    what the grid *shape* buys on hardware: per-cell work is the wall
    time divided by the cells actually run, and a chip with ``n_cores``
    grid lanes needs ``ceil(cells / n_cores)`` sequential rounds — so
    ``proxy tok/s = B * cells / (wall * rounds)``.  More splits shrink
    per-cell work (fewer pages each) until the lanes fill; the analytic
    cost model must predict the same crossover (``predicted_best_splits``)
    from the census's ``grid_cells`` utilization term alone.  Greedy
    tokens (argmax through a fixed random readout) must be byte-identical
    across split, unsplit, and oracle in every cell.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.autotune.search import Autotuner
    from repro.core.autotune.space import get_tunable
    from repro.core.costmodel import CostModel
    from repro.kernels import ops
    from repro.kernels.ref import paged_attention_ref

    ctx, num_splits = int(params["ctx"]), int(params["num_splits"])
    # one long sequence, small batch: 4 grid cells unsplit, far below the
    # modeled lane count — the regime splits exist for.  Pages are kept
    # large enough (bs x D) that per-page streaming dominates the
    # interpreter's per-cell dispatch overhead, or the proxy would
    # understate what the grid shape buys.
    B, H, KH, D, bs = 1, 4, 2, 128, 32
    nb = -(-ctx // bs)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, D)) * 0.3, jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(B * nb, bs, KH, D)) * 0.3,
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(B * nb, bs, KH, D)) * 0.3,
                          jnp.float32)
    bt = jnp.asarray(rng.permutation(B * nb).reshape(B, nb).astype(np.int32))
    lens = jnp.full((B,), ctx, jnp.int32)
    readout = jnp.asarray(rng.normal(size=(H * D, 256)), jnp.float32)

    cm = CostModel.from_named("tpu_v5e")
    lanes = max(int(getattr(cm.hw, "n_cores", 1)), 1)

    def run(ns):
        # hbm=True: the production lowering — per-page DMA, so each cell
        # only pays for the pages its split reads.  The staged lowering
        # would copy the WHOLE pool into every grid cell under interpret
        # mode, burying the split signal in per-cell staging cost.
        return ops.paged_attention(q, k_pages, v_pages, bt, lens,
                                   num_splits=ns, hbm=True)

    def greedy(out):
        logits = out.reshape(B, H * D) @ readout
        return np.asarray(jnp.argmax(logits, axis=-1)).tolist()

    def wall_s(ns):
        jax.block_until_ready(run(ns))            # compile + warm
        iters = 2 if quick else 5
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(run(ns))
        return (time.perf_counter() - t0) / iters

    def proxy_tok_s(wall, ns):
        cells = B * H * max(ns, 1)
        rounds = -(-cells // lanes)
        return B * cells / max(wall * rounds, 1e-12)

    # analytic ranking over the split ladder at this cell's layout — the
    # cost model's predicted crossover, and what the tuning cache would
    # install for this context bucket
    tn = get_tunable("paged_attention")
    shapes = {"batch": B, "heads": H, "kv_heads": KH, "head_dim": D,
              "ctx": ctx}

    def predict_s(ns):
        census = dict(tn.census(shapes, {"block_size": bs,
                                         "num_splits": ns}, "f32"))
        census.pop("mxu_shape", None)
        return cm.predict(census, dtype="f32").step_s

    ladder = [s for s in (1, 2, 4, 8, 16) if s <= nb]
    pred = {s: predict_s(s) for s in ladder}
    predicted_best_splits = min(ladder, key=lambda s: (pred[s], s))

    # the real tuner ranks the same space through the cache-key path
    # (shape bucket includes ctx, so contexts tune independently)
    tuner = Autotuner(cm, dtype="f32")
    tuned = tuner.tune("paged_attention", shapes)

    w_this = wall_s(num_splits)
    w_unsplit = w_this if num_splits == 1 else wall_s(1)
    w_tuned = (w_this if predicted_best_splits == num_splits
               else wall_s(predicted_best_splits))
    out_this, out_unsplit = run(num_splits), run(1)
    oracle = paged_attention_ref(q, k_pages, v_pages, bt, lens)
    toks = greedy(out_this)
    identical = (toks == greedy(out_unsplit) == greedy(oracle))

    this_tok_s = proxy_tok_s(w_this, num_splits)
    unsplit_tok_s = proxy_tok_s(w_unsplit, 1)
    tuned_tok_s = proxy_tok_s(w_tuned, predicted_best_splits)
    return {
        "ctx": ctx, "num_splits": num_splits, "lanes": lanes,
        "wall_us": w_this * 1e6,
        "proxy_tok_s": this_tok_s,
        "unsplit_proxy_tok_s": unsplit_tok_s,
        "speedup": this_tok_s / max(unsplit_tok_s, 1e-12),
        "tuned_splits": predicted_best_splits,
        "tuned_proxy_tok_s": tuned_tok_s,
        "tuned_speedup": tuned_tok_s / max(unsplit_tok_s, 1e-12),
        "predicted_s": pred[num_splits] if num_splits in pred
        else predict_s(num_splits),
        "predicted_unsplit_s": pred[1],
        "predicted_speedup": pred[1] / max(
            pred.get(num_splits, predict_s(num_splits)), 1e-30),
        "predicted_best_splits": predicted_best_splits,
        "tuner_best_config": dict(tuned.best),
        "tuner_cache_key": tuned.key,
        "identical_tokens": bool(identical),
        "max_abs_err_vs_ref": float(jnp.max(jnp.abs(out_this - oracle))),
    }


register(Experiment(
    name="decode_longctx",
    description="split-KV flash-decoding: context length x split factor, "
                "measured lane-utilization proxy tok/s vs the unsplit "
                "kernel, analytic crossover prediction, greedy-token "
                "equality vs the oracle",
    grid={"ctx": (256, 1024, 4096), "num_splits": (1, 2, 4, 8)},
    quick_grid={"ctx": (128, 512), "num_splits": (1, 2, 4)},
    runner=run_decode_longctx_cell,
    cost_per_cell_s=15.0,
    tags=("serve", "kernels", "longctx"),
))

def run_traffic_scaling_cell(params: Dict[str, Any], quick: bool = False
                             ) -> Dict[str, Any]:
    """The cluster tier under offered load: one skewed trace (every
    ``period``-th request long, period = replica count, so round-robin
    piles the long ones onto one replica) served by an N-replica
    ``ServingCluster`` on REAL arrays under the parallel-replica virtual
    clock, once per placement policy.  Reports tok/s, p50/p99 latency,
    shed rate, reroute/preemption counts, token conservation, and the
    cost-model-chosen topology for the device budget — the artifact that
    has to show cost-aware placement beating round-robin."""
    import time

    import jax
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.configs.base import ShapeCell
    from repro.core.costmodel import CostModel
    from repro.models.zoo import build_model
    from repro.serve import PagedServingEngine
    from repro.serve.cluster import ServingCluster, serve_trace, skewed_trace
    from repro.serve.sim import SimClock
    from repro.sharding.plans import rank_cluster_topologies

    r = int(params["replicas"])
    load = float(params["load"])
    n_req = (4 * r if quick else 8 * r)
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    weights = model.init(jax.random.PRNGKey(0))
    cm = CostModel.from_named("tpu_v5e")
    max_batch, max_len, bs, chunk = 4, 64, 8, 16
    # per-replica pool: ~60% of the slot-equivalent rectangle, same ratio
    # as paged_serve — tight enough that a long-request pileup preempts
    n_blocks = max(-(-max_len // bs),
                   int(0.6 * max_batch * (-(-max_len // bs))))
    period = max(r, 2)

    def build_cluster(policy):
        # simulated replicas: all on this host's default device, stepped
        # on one SimClock (``ServingCluster.build`` needs a device each)
        clock = SimClock()
        replicas = [PagedServingEngine(
            model, weights, clock=clock, cost_model=cm,
            max_batch=max_batch, max_len=max_len, block_size=bs,
            n_blocks=n_blocks, chunk_size=chunk) for _ in range(r)]
        cl = ServingCluster(
            replicas, policy=policy,
            shed_wait_s=float(params.get("shed_wait_s", 30.0)))
        return cl, clock

    # calibrate the arrival gap to this machine: warm one engine (each
    # engine instance compiles its own step closures), then price one
    # steady-state step with a second warmed instance
    interval_s = None
    rng = np.random.default_rng(0)
    warm_prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
                    for _ in range(2)]
    for _ in range(2):
        eng = PagedServingEngine(model, weights, max_batch=max_batch,
                                 max_len=max_len, block_size=bs,
                                 n_blocks=n_blocks, chunk_size=chunk)
        for p in warm_prompts:
            eng.submit(p, max_new_tokens=4)
        t0 = time.perf_counter()
        st = eng.run_until_done(max_steps=20_000)
        interval_s = max((time.perf_counter() - t0) / max(st.steps, 1),
                         1e-5)

    out: Dict[str, Any] = {
        "replicas": r, "load": load, "n_requests": n_req,
        "interval_s": interval_s, "n_blocks_per_replica": n_blocks,
    }
    trace = skewed_trace(n_req, vocab=cfg.vocab_size, period=period,
                         long_len=32, short_len=4, long_new=16, short_new=4,
                         interval_s=interval_s, load=load)
    tokens_by_policy: Dict[str, Dict[int, list]] = {}
    for key, policy in (("rr", "round_robin"), ("ca", "cost_aware")):
        cl, clock = build_cluster(policy)
        # warm every replica (per-instance jit) OUTSIDE the router so the
        # timed trace measures steady-state decode, then rewind the clock
        for eng in cl.replicas:
            for p in warm_prompts:
                eng.submit(p, max_new_tokens=4)
            eng.run_until_done(max_steps=20_000)
        clock.t = 0.0
        admitted = serve_trace(cl, trace, clock, min_dt=interval_s / 4,
                               max_ticks=50_000)
        wall = max(clock.t, 1e-9)
        toks = sum(len(q.tokens) for q in cl.done.values())
        lats = sorted(cl.done[c].finished_s - admitted[c] for c in cl.done)
        grab = lambda q: lats[int(q * (len(lats) - 1))] if lats else 0.0
        conserved = (len(cl.done) == len(admitted)
                     and all(len(q.tokens) == q.max_new_tokens
                             for q in cl.done.values()))
        if conserved:
            # drained-trace invariant: every per-request router dict
            # (_local/_origin/_moves) must be pruned, or a long-running
            # cluster leaks bookkeeping per request
            cl.router.assert_drained()
        tokens_by_policy[key] = {
            round(admitted[c] / (interval_s / load)): list(cl.done[c].tokens)
            for c in cl.done}           # trace index -> tokens
        out.update({
            f"{key}_tok_per_s": toks / wall,
            f"{key}_p50_s": grab(0.50),
            f"{key}_p99_s": grab(0.99),
            f"{key}_shed_rate": cl.stats.shed / max(len(trace), 1),
            f"{key}_completed": len(cl.done),
            f"{key}_reroutes": cl.stats.reroutes,
            f"{key}_preemptions": sum(e.stats.preemptions
                                      for e in cl.replicas),
            f"{key}_conserved": bool(conserved),
        })

    # greedy decode is deterministic per request, so the two policies must
    # produce byte-identical tokens for every trace index both admitted
    shared = set(tokens_by_policy["rr"]) & set(tokens_by_policy["ca"])
    out["identical_tokens"] = all(
        tokens_by_policy["rr"][i] == tokens_by_policy["ca"][i]
        for i in shared)
    if r == 1:
        # ...and at one replica the cluster must be byte-identical to a
        # bare paged engine fed the same prompts
        eng = PagedServingEngine(model, weights, max_batch=max_batch,
                                 max_len=max_len, block_size=bs,
                                 n_blocks=n_blocks, chunk_size=chunk)
        rids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=new,
                           eos_id=eos) for _, p, new, eos in trace]
        eng.run_until_done(max_steps=50_000)
        bare = {i: list(eng.done[rid].tokens) for i, rid in enumerate(rids)}
        out["identical_tokens"] = out["identical_tokens"] and all(
            tokens_by_policy["ca"][i] == bare[i]
            for i in tokens_by_policy["ca"])
    out["speedup_tok_s"] = (out["ca_tok_per_s"]
                            / max(out["rr_tok_per_s"], 1e-9))
    out["p99_ratio"] = out["rr_p99_s"] / max(out["ca_p99_s"], 1e-9)

    # what the calibrated cost model would buy with an r-device budget
    cell = ShapeCell("cluster", "decode", max_len, max_batch)
    top = rank_cluster_topologies(cfg, cell, r, cm)[0]
    out["topology_replicas"] = top.n_replicas
    out["topology_data"] = top.plan.data
    out["topology_model"] = top.plan.model
    out["topology_pred_tok_s"] = top.predicted_tok_s
    return out


def run_sharded_decode_cell(params: Dict[str, Any], quick: bool = False
                            ) -> Dict[str, Any]:
    """Sharded intra-replica decode: the acceptance comparison plus the
    measured-vs-predicted step time per (data, model) factorization.

    Runs ``serve.sharded_check`` in a subprocess with a forced
    multi-device CPU host (the flag must precede jax init, so it cannot
    run in this process): a paged replica on each candidate mesh serves
    the 32-request acceptance trace and must be byte-identical to the
    single-device engine with the one-sync and donation invariants
    intact.  Reported per shape: measured wall-clock per step alongside
    ``rank_plans``' predicted step time — the measured CPU numbers
    validate the *mechanism*, the predictions carry the priced-TPU
    ordering the mesh choice is based on."""
    from repro.serve.sharded_check import parse_shapes, run_subprocess

    shapes = parse_shapes(params["shapes"])
    doc = run_subprocess(shapes, devices=int(params.get("devices", 8)),
                         n_req=8 if quick else 32)
    out: Dict[str, Any] = {
        "shapes": params["shapes"], "devices": doc["devices"],
        "n_req": doc["n_req"], "ref_step_s": doc["reference"]["step_s"],
        "identical_all": bool(doc["ok"]),
    }
    for s in doc["shapes"]:
        if s.get("skipped"):
            continue
        key = f"d{s['data']}m{s['model']}"
        out[f"{key}_step_s"] = s["step_s"]
        out[f"{key}_pred_step_s"] = s["predicted_step_s"]
        out[f"{key}_identical"] = bool(s["identical"])
        out[f"{key}_donated"] = bool(s["donated"])
        out[f"{key}_sync_ok"] = bool(s["sync_per_step_ok"])
        out[f"{key}_preemptions"] = s["preemptions"]
        out[f"{key}_compactions"] = s["compactions"]
    return out


register(Experiment(
    name="sharded_decode",
    description="sharded intra-replica decode: paged replicas on "
                "(data, model) meshes of a forced multi-device CPU host "
                "serve the acceptance trace byte-identically to the "
                "single-device engine, with measured vs cost-model-"
                "predicted step time per factorization",
    grid={"shapes": ("1x1,2x1,1x2,2x2",)},
    quick_grid={"shapes": ("1x1,1x2",)},
    runner=run_sharded_decode_cell,
    # a CPU-mesh correctness check: on an accelerator the child would
    # report forced CPU devices under this name, so it refuses instead
    backends=("cpu",),
    cost_per_cell_s=300.0,
    tags=("serve", "sharding", "costmodel"),
))


def run_chaos_serving_cell(params: Dict[str, Any], quick: bool = False
                           ) -> Dict[str, Any]:
    """One chaos drill: a seeded fault of ``params['fault']`` injected
    into a ``params['replicas']``-wide paged cluster under SimClock,
    with detection (heartbeats / straggler ceiling / integrity probe),
    router-level request recovery and restart-budget rejoin — then the
    recovery invariants checked against a fault-free twin of the same
    trace (see ``repro.serve.chaos.drill``).  ``ok`` summarizes the
    cell's gate: identical survivors, all requests accounted, zero lost
    tokens, zero leaked blocks, at least one fault actually detected —
    and, for ``crashloop``, the breaker quarantining the flapper."""
    from repro.serve.chaos.drill import run_chaos_drill
    fault = str(params["fault"])
    replicas = int(params["replicas"])
    out = run_chaos_drill(fault, replicas,
                          n_requests=8 if quick else 12)
    ok = (out["survivors_identical"] and out["all_accounted"]
          and out["tokens_lost"] == 0 and out["blocks_leaked"] == 0
          and out["failures"] >= 1)
    if fault == "crashloop":
        ok = ok and out["quarantined"]
    out["ok"] = bool(ok)
    return out


register(Experiment(
    name="chaos_serving",
    description="deterministic fault drills on the serving cluster: "
                "crash / hang / corrupt / crash-loop x replica count "
                "under SimClock — heartbeat+straggler+integrity "
                "detection, router request recovery with retry budget, "
                "brownout admission, restart-budget quarantine; gates "
                "byte-identical survivors, zero lost tokens, zero "
                "leaked blocks, drained router",
    grid={"fault": ("crash", "hang", "corrupt", "crashloop"),
          "replicas": (2, 3)},
    quick_grid={"fault": ("crash", "hang", "corrupt", "crashloop"),
                "replicas": (2,)},
    runner=run_chaos_serving_cell,
    cost_per_cell_s=30.0,
    tags=("serve", "cluster", "chaos"),
))


register(Experiment(
    name="traffic_scaling",
    description="multi-replica cluster under offered load x replica "
                "count: skewed trace served round-robin vs cost-aware "
                "placement on real arrays under the parallel-replica "
                "virtual clock — tok/s, p50/p99 latency, shed rate, "
                "reroutes, token conservation, chosen topology",
    grid={"replicas": (1, 2, 4), "load": (1.0, 2.0)},
    quick_grid={"replicas": (1, 2), "load": (2.0,)},
    runner=run_traffic_scaling_cell,
    cost_per_cell_s=60.0,
    tags=("serve", "cluster", "costmodel"),
))
