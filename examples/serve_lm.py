"""Batched serving example: continuous batching through the engine with
cost-model-gated admission — predicted decode-step latency decides how many
prefills pack into each engine iteration — plus latency/throughput
accounting per request, then the same trace through the PAGED engine
(block-pool KV cache, chunked prefill) for a like-for-like comparison of
tokens, KV bytes resident and preemption behaviour.  The paged run
streams per-step/per-request telemetry into a MetricsSink (summary
printed, snapshot saved under results/ — see docs/ops-runbook.md for
how to read it).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.costmodel import CostModel
from repro.models.zoo import build_model
from repro.serve.engine import PagedServingEngine, ServingEngine
from repro.serve.telemetry import TelemetryController


def main():
    cfg = reduced(ARCHS["gemma3-1b"], n_layers=4, d_model=128, n_heads=4,
                  n_kv_heads=1, head_dim=32, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cm = CostModel.from_named("tpu_v5e")
    # a tight budget: admissions beyond the first per step defer until the
    # predicted iteration time (decode + prefills) fits again; the
    # controller only records (drift=False: no recalibration)
    slot_ctl = TelemetryController(drift=False)
    eng = ServingEngine(model, params, max_batch=4, max_len=96,
                        cost_model=cm, step_budget_s=5e-5,
                        telemetry=slot_ctl)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=rng.integers(4, 24)).astype(np.int32)
               for _ in range(10)]
    t0 = time.time()
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    stats = eng.run_until_done()
    dt = time.time() - t0

    print(f"completed {stats.completed} requests / "
          f"{stats.decoded_tokens} tokens in {dt:.2f}s "
          f"({stats.decoded_tokens/dt:.1f} tok/s, "
          f"{stats.steps} decode steps, {stats.prefills} prefills, "
          f"{stats.deferred_prefills} admissions deferred, "
          f"{stats.host_syncs/max(stats.steps, 1):.2f} host syncs/step "
          "on the fused hot path)")
    steps = slot_ctl.sink.steps()
    pred = [s.predicted_s for s in steps]
    print(f"  predicted step time: {min(pred):.2e}-{max(pred):.2e}s "
          f"(measured median "
          f"{np.median([s.measured_s for s in steps]):.2e}s)")
    for rid in rids[:3]:
        r = eng.done[rid]
        print(f"  req {rid}: prompt[{len(r.prompt)}] -> {r.tokens}")
    assert stats.completed == 10

    # the same trace, paged: a block pool sized at ~half the slot engine's
    # max_batch x max_len rectangle, prompts prefilled in 16-token chunks;
    # a telemetry controller streams per-step/per-request records
    ctl = TelemetryController()
    paged = PagedServingEngine(model, params, max_batch=4, max_len=96,
                               block_size=16, n_blocks=12, chunk_size=16,
                               telemetry=ctl)
    t0 = time.time()
    prids = [paged.submit(p, max_new_tokens=12) for p in prompts]
    pstats = paged.run_until_done()
    pdt = time.time() - t0
    print(f"paged: {pstats.completed} requests in {pdt:.2f}s "
          f"({pstats.decoded_tokens/pdt:.1f} tok/s, "
          f"{pstats.prefill_chunks} chunks, {pstats.preemptions} "
          f"preemptions, peak {pstats.peak_blocks_in_use}/"
          f"{paged.n_blocks} blocks)")
    print(f"  KV bytes resident: slot={eng.kv_cache_bytes()} "
          f"paged={paged.kv_cache_bytes()} "
          f"({paged.kv_cache_bytes()/eng.kv_cache_bytes():.0%})")
    identical = all(eng.done[a].tokens == paged.done[b].tokens
                    for a, b in zip(rids, prids))
    print(f"  greedy tokens identical: {identical}")
    occ = [r.blocks_in_use / r.n_blocks for r in ctl.sink.steps()]
    print(f"  block occupancy per step: mean {np.mean(occ):.0%}, "
          f"peak {max(occ):.0%}")
    s = ctl.sink.summary()
    snap = ctl.sink.save("results/telemetry/serve_lm_snapshot.json")
    print(f"  telemetry: {s['steps']} steps recorded, "
          f"step p50/p99 {s['step_p50_s']:.2e}/{s['step_p99_s']:.2e}s, "
          f"request p99 {s['request_p99_s']:.2e}s -> {snap}")
    assert identical and pstats.completed == 10
    assert s["steps"] == pstats.steps and s["requests"] == pstats.completed
    print("serve_lm OK")


if __name__ == "__main__":
    main()
