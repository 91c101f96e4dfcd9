"""The sharded replica's acceptance check, runnable in-process or as a
subprocess with a forced multi-device CPU host.

The tentpole contract (docs/architecture.md, "Sharded replicas"): a
:class:`~repro.serve.engine.PagedServingEngine` built on a
``('data', 'model')`` mesh must produce greedy tokens BYTE-IDENTICAL to
the single-device engine on the acceptance trace — sharding the KV pool
over KV heads and the loop state over batch rows is a layout change,
never a numerics change — while keeping the fused path's invariants
(<= 1 host sync per step, donated pool).

CPU hosts have one device unless XLA is told otherwise, and the flag
must be set BEFORE jax initializes — so the check ships a subprocess
runner (``run_subprocess``) that re-enters this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and parses the
JSON the child prints.  Three consumers share it: the
``test_sharded_decode.py`` suite, the ``sharded_decode`` campaign
experiment (measured-vs-predicted step time per factorization), and the
CI multi-device smoke job (which sets the flag itself and runs
``python -m repro.serve.sharded_check``).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

ENGINE_KW = dict(max_batch=4, max_len=48, block_size=8, n_blocks=10,
                 chunk_size=8)   # tight pool: evictions + compactions fire


def acceptance_trace(cfg, n_req: int = 32, seed: int = 11,
                     max_prompt: int = 31) -> List[np.ndarray]:
    """THE 32-request acceptance trace (same generator as the
    decode-hotpath suite): random prompts of 1..max_prompt tokens."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(1, max_prompt))
                         ).astype(np.int32) for _ in range(n_req)]


def _run_trace(eng, prompts, max_new: int):
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_done(max_steps=20_000)
    return [eng.done[r].tokens for r in rids]


def parse_shapes(text: str) -> List[Tuple[int, int]]:
    """'1x1,2x1,2x2' -> [(1, 1), (2, 1), (2, 2)] (data x model)."""
    out = []
    for part in text.split(","):
        d, m = part.lower().split("x")
        out.append((int(d), int(m)))
    return out


def _kernel_check(devs) -> Optional[bool]:
    """Cross-check ``paged_attention_sharded``'s shard_map route against
    the unsharded kernel on a (2, 2) mesh — the head/batch index-space
    split must be invisible in the outputs.  None when the host has too
    few devices to build the mesh (nothing to check)."""
    if len(devs) < 4:
        return None
    import jax.numpy as jnp

    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_sharded)
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(3)
    B, H, KH, D, bs, pages = 4, 8, 4, 16, 8, 12
    q = jnp.asarray(rng.normal(size=(B, H, D)) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.normal(size=(pages, bs, KH, D)) * 0.3, jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pages, bs, KH, D)) * 0.3, jnp.float32)
    bt = jnp.asarray(rng.permutation(pages)[:B * 3].reshape(B, 3), jnp.int32)
    ctx = jnp.asarray([5, 24, 17, 1], jnp.int32)
    mesh = make_host_mesh(model_axis=2, devices=devs[:4])
    o = paged_attention_sharded(q, kp, vp, bt, ctx, mesh, interpret=True)
    r = paged_attention(q, kp, vp, bt, ctx, interpret=True)
    return bool(np.allclose(np.asarray(o), np.asarray(r), atol=1e-5))


def run_check(shapes: Sequence[Tuple[int, int]], *, n_req: int = 32,
              max_new: int = 4, predict: bool = True) -> dict:
    """Run the acceptance comparison in THIS process (the caller is
    responsible for the device count — see ``run_subprocess``).

    Returns a JSON-able doc: the single-device reference run plus, per
    (data, model) shape, token equality, the sync/donation invariants,
    eviction/compaction coverage, measured wall-clock per step and the
    cost model's predicted step time for that factorization."""
    import jax

    from repro.configs import ARCHS, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models.zoo import build_model
    from repro.serve.engine import PagedServingEngine

    if jax.default_backend() != "cpu":
        # the meshes are forced host devices; on an accelerator this
        # would report a different program under the same name
        raise RuntimeError("sharded_check runs on a forced multi-device "
                           f"CPU host, not on {jax.default_backend()!r}; "
                           "set JAX_PLATFORMS=cpu")
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = acceptance_trace(cfg, n_req=n_req)
    devs = jax.devices()

    t0 = time.perf_counter()
    ref_eng = PagedServingEngine(model, params, fused=True, **ENGINE_KW)
    ref = _run_trace(ref_eng, prompts, max_new)
    ref_wall = time.perf_counter() - t0

    doc = {"devices": len(devs), "arch": cfg.name, "n_req": n_req,
           "max_new": max_new,
           "reference": {"steps": ref_eng.stats.steps,
                         "host_syncs": ref_eng.stats.host_syncs,
                         "wall_s": ref_wall,
                         "step_s": ref_wall / max(ref_eng.stats.steps, 1)},
           "shapes": [], "ok": True}

    preds = {}
    if predict:
        from repro.configs.base import ShapeCell
        from repro.sharding.plans import rank_plans
        cell = ShapeCell("sharded", "decode", ENGINE_KW["max_len"],
                         ENGINE_KW["max_batch"])
        for n in {d * m for d, m in shapes}:
            for plan in rank_plans(cfg, cell, n):
                preds[(plan.data, plan.model)] = plan.step_s

    for d, m in shapes:
        need = d * m
        if need > len(devs):
            doc["shapes"].append({"data": d, "model": m,
                                  "skipped": f"needs {need} devices, "
                                             f"have {len(devs)}"})
            continue
        mesh = make_host_mesh(model_axis=m, devices=devs[:need])
        t0 = time.perf_counter()
        eng = PagedServingEngine(model, params, fused=True, mesh=mesh,
                                 **ENGINE_KW)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        old_pool = jax.tree.leaves(eng.cache)
        with jax.transfer_guard_device_to_host("disallow"):
            eng.step()
            donated = all(x.is_deleted() for x in old_pool)
            eng.run_until_done(max_steps=20_000)
        wall = time.perf_counter() - t0
        toks = [eng.done[r].tokens for r in sorted(eng.done)]
        entry = {
            "data": d, "model": m,
            "identical": toks == ref,
            "steps": eng.stats.steps,
            "host_syncs": eng.stats.host_syncs,
            "sync_per_step_ok": eng.stats.host_syncs <= eng.stats.steps,
            "donated": donated,
            "preemptions": eng.stats.preemptions,
            "compactions": eng.stats.compactions,
            "wall_s": wall,
            "step_s": wall / max(eng.stats.steps, 1),
            "predicted_step_s": preds.get((d, m)),
            "sharding_log": eng.sharding_log,
        }
        entry["ok"] = bool(entry["identical"] and entry["sync_per_step_ok"]
                           and entry["donated"])
        doc["ok"] = doc["ok"] and entry["ok"]
        doc["shapes"].append(entry)
    doc["kernel_sharded_ok"] = _kernel_check(devs)
    doc["ok"] = doc["ok"] and doc["kernel_sharded_ok"] is not False
    return doc


def run_subprocess(shapes: Sequence[Tuple[int, int]], *, devices: int = 8,
                   n_req: int = 32, max_new: int = 4,
                   timeout_s: float = 1200.0) -> dict:
    """Re-enter this module in a child process with
    ``--xla_force_host_platform_device_count=<devices>`` set before jax
    initializes there, and return the parsed JSON doc.  The child runs
    on the CPU and refuses any other backend (``run_check``), so the
    caller runs with ``JAX_PLATFORMS=cpu``."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith("--xla_force_host_platform"))
    env["XLA_FLAGS"] = (flags + " "
                       f"--xla_force_host_platform_device_count={devices}"
                       ).strip()
    shape_arg = ",".join(f"{d}x{m}" for d, m in shapes)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.serve.sharded_check",
         "--shapes", shape_arg, "--n-req", str(n_req),
         "--max-new", str(max_new)],
        capture_output=True, text=True, env=env, timeout=timeout_s)
    if proc.returncode not in (0, 1):   # 1 = ran but a contract failed
        raise RuntimeError(
            f"sharded_check subprocess died (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="sharded-replica acceptance check (JSON to stdout)")
    ap.add_argument("--shapes", default="1x1,2x1,1x2,2x2",
                    help="comma-separated dataxmodel factorizations")
    ap.add_argument("--n-req", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--no-predict", action="store_true",
                    help="skip cost-model predictions (faster)")
    args = ap.parse_args(argv)
    doc = run_check(parse_shapes(args.shapes), n_req=args.n_req,
                    max_new=args.max_new, predict=not args.no_predict)
    print(json.dumps(doc))
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
