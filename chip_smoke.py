#!/usr/bin/env python3
"""Serve internlm2-20b at its published widths on TPU: the quickest proof
that the serving path still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded replica + 4-replica router

One chip: ``build_model`` -> ``PagedServingEngine`` (fused, donated
step, the paged-attention kernel) -> ``submit`` x 8 -> ``run_until_done``,
then three checks: every request completed with its token count inside
the vocabulary, the compiled decode step holds the Pallas kernel
(``tpu_custom_call``), and the kernel matches the ``kernels/ref.py``
oracle at the served shapes.  Four chips: the same model as one replica
sharded over a ``(data=1, model=4)`` mesh against the one-chip engine,
and ``ServingCluster`` with four one-chip replicas, each on its own
device.  Nothing else runs with ``--chips 4``.

The widths are the published ones (d_model 6144, 48 query heads over 8
KV heads of 128, d_ff 16384, vocab 92544, untied embeddings); only the
depth is cut, to ``KEEP_LAYERS`` of 48: params are f32 (``param_dtype``
is not read by the model), 1.56 GB a layer plus 4.55 GB of embedding
and head, and the compiled 3-layer decode step needs 10.9 GiB of the
chip's 16 GiB (4 layers: 13.1 GiB, too close to leave room for the
four-chip comparison).  Weights are random from ``SEED``.

Any failed check exits nonzero.  The last line of stdout is the JSON
verdict; the lines before it are diagnostics, and their wall times are
smoke timings, not metrics.  The script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "internlm2-20b"
KEEP_LAYERS = 3
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = (128, 512)          # inclusive range of prompt lengths
MAX_NEW = 32
BLOCK = 16                       # paged-pool block size (explicit: no tuner)
MAX_LEN = 576                    # >= longest prompt + MAX_NEW + 1
CHUNK = 128                      # chunked-prefill width
BF16_TOL = 2e-2                  # the repo's bf16 tolerance (test_kernels)


def fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileWatch:
    """Sums backend-compile seconds (persistent-cache reads included)
    and counts persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _nbytes(tree) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def make_engine(model, params, mesh=None):
    from repro.serve.engine import PagedServingEngine
    n_blocks = N_REQUESTS * -(-MAX_LEN // BLOCK)
    return PagedServingEngine(model, params, max_batch=N_REQUESTS,
                              max_len=MAX_LEN, block_size=BLOCK,
                              n_blocks=n_blocks, chunk_size=CHUNK,
                              fused=True, mesh=mesh)


def make_prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lens]


def serve(server, prompts, vocab: int):
    """Submit ``prompts``, run them to completion, check every request
    and return the token streams in submission order."""
    rids = [server.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    server.run_until_done()
    wall = time.perf_counter() - t0
    toks = []
    for rid in rids:
        check(rid in server.done, f"request {rid} did not complete")
        t = list(server.done[rid].tokens)
        check(len(t) == MAX_NEW,
              f"request {rid} produced {len(t)} tokens, expected {MAX_NEW}")
        check(all(0 <= x < vocab for x in t),
              f"request {rid} produced a token outside [0, {vocab})")
        toks.append(t)
    print(f"served {len(rids)} requests, {sum(map(len, toks))} tokens; "
          f"run_until_done wall {wall:.3f} s (smoke timing, not a metric)")
    return toks


def init_params(model):
    """Random f32 params made on the device by a jitted ``init``."""
    import jax
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    print(f"params: {_nbytes(params) / 1e9:.3f} GB made on device in "
          f"{time.perf_counter() - t0:.3f} s (smoke timing)")
    return params


def check_kernel_vs_oracle(model, eng, prompts) -> None:
    """``ops.paged_attention`` at the served shapes (batch, heads, pool,
    block tables, the served context lengths) against ``kernels/ref.py``,
    for ``num_splits`` 1, the engine-resolved factor and 4."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    cfg = model.cfg
    B, H, KH, D = N_REQUESTS, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    P, NB = eng.n_blocks, eng.max_blocks_per_seq
    ctx = np.asarray([len(p) + MAX_NEW for p in prompts], np.int32)
    rng = np.random.default_rng(SEED + 1)
    bt = rng.permutation(P)[:B * NB].astype(np.int32).reshape(B, NB)
    bt[np.arange(NB)[None] >= -(-ctx[:, None] // BLOCK)] = -1
    keys = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (P, BLOCK, KH, D), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (P, BLOCK, KH, D), jnp.bfloat16)
    shapes = {"batch": B, "heads": H, "kv_heads": KH, "head_dim": D,
              "ctx": NB * BLOCK}
    resolved = ops.resolve_kernel_config("paged_attention", shapes,
                                         jnp.bfloat16, tuned=True)
    resolved = max(min(int(resolved["num_splits"]), NB), 1)
    want = np.asarray(jax.jit(ref.paged_attention_ref)(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), bt, ctx), np.float32)
    for ns in sorted({1, resolved, 4}):
        got = np.asarray(ops.paged_attention(q, kp, vp, bt, ctx,
                                             num_splits=ns), np.float32)
        err = float(np.max(np.abs(got - want)))
        print(f"paged kernel vs oracle, num_splits={ns}"
              f"{' (engine-resolved)' if ns == resolved else ''}: "
              f"max |err| {err:.3e} (tolerance {BF16_TOL})")
        check(np.isfinite(got).all() and err <= BF16_TOL,
              f"paged kernel (num_splits={ns}) off the oracle by {err}")


def one_chip(model, params) -> None:
    import jax
    import numpy as np

    vocab = model.cfg.vocab_size
    eng = make_engine(model, params)
    print(f"paged pool: {eng.kv_cache_bytes() / 1e6:.3f} MB "
          f"({eng.n_blocks} blocks x {BLOCK} slots x {eng.model.cfg.n_layers}"
          f" layers)")
    prompts = make_prompts(vocab)
    print(f"requests: {len(prompts)}, prompt lengths "
          f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each")
    serve(eng, prompts, vocab)
    st = eng.stats
    syncs = st.host_syncs / max(st.steps, 1)
    print(f"engine steps {st.steps}, prefill chunks {st.prefill_chunks}, "
          f"host syncs per step {syncs:.3f}")
    check(syncs <= 1.0, f"{syncs} host syncs per step (fused path: <= 1)")

    # the compiled decode step, as the engine jits it
    toks = np.zeros(N_REQUESTS, np.int32)
    bt = np.full((N_REQUESTS, eng.max_blocks_per_seq), -1, np.int32)
    hlo = eng._decode.lower(eng.params, eng.cache, toks, toks,
                            bt).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    print(f"compiled decode step holds tpu_custom_call: {has_kernel}")
    check(has_kernel, "the decode step ran the jnp gather, not the kernel")

    check_kernel_vs_oracle(model, eng, prompts)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


def first_step_logits(model, params, prompts, mesh=None):
    """Logits [B, V] of the first decode step after prefilling the
    equal-length ``prompts`` through ``model.decode``'s paged path, on
    one device or per shard of ``mesh`` (the engine's kernel route)."""
    import jax
    import numpy as np
    from repro.sharding import ctx

    batch = np.stack(prompts)
    B, S = batch.shape
    nb = -(-(S + 1) // BLOCK)
    pool = (model.init_paged_cache(B * nb, BLOCK, mesh=mesh)
            if mesh is not None else model.init_paged_cache(B * nb, BLOCK))
    bt = np.arange(B * nb, dtype=np.int32).reshape(B, nb)

    def decode(params, cache, tokens, pos, bt):
        with ctx.use_kernel_mesh(mesh):
            return model.decode(params, cache, tokens, pos, bt)

    step = jax.jit(decode, donate_argnums=(1,))
    logits, pool = step(params, pool, batch, np.zeros(B, np.int32), bt)
    nxt = np.asarray(logits).argmax(-1).astype(np.int32)[:, None]
    logits, _ = step(params, pool, nxt, np.full(B, S, np.int32), bt)
    return np.asarray(logits, np.float32)


def four_chips(model, params) -> None:
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.serve.cluster import ServingCluster

    devs = jax.devices()
    vocab = model.cfg.vocab_size
    prompts = make_prompts(vocab)
    same_len = [p[:PROMPT_LEN[0]] for p in prompts]

    # the reference: the one-chip engine, on device 0
    ref_logits = first_step_logits(model, params, same_len)
    ref_tokens = serve(make_engine(model, params), prompts, vocab)

    # the sharded replica: the same model over (data=1, model=4)
    mesh = make_host_mesh(model_axis=4)
    eng = make_engine(model, params, mesh=mesh)
    print(f"sharded replica mesh {dict(mesh.shape)}, sharding log "
          f"{eng.sharding_log or 'empty'}")
    logits = first_step_logits(model, eng.params, same_len, mesh)
    scale = float(np.max(np.abs(ref_logits)))
    err = float(np.max(np.abs(logits - ref_logits)))
    print(f"sharded vs one-chip first decode-step logits: max |err| "
          f"{err:.3e}, logit scale {scale:.3e}, tolerance "
          f"{BF16_TOL} x scale")
    print(f"first decode-step greedy agreement: "
          f"{np.mean(logits.argmax(-1) == ref_logits.argmax(-1)):.3f}")
    check(np.isfinite(logits).all() and err <= BF16_TOL * scale,
          f"sharded logits off the one-chip engine's by {err}")
    tokens = serve(eng, prompts, vocab)
    agree = np.mean(np.asarray(tokens) == np.asarray(ref_tokens))
    print(f"sharded vs one-chip served tokens agreement: {agree:.3f}")
    del eng

    # the router: four one-chip replicas, each on its own device
    cluster = ServingCluster.build(
        model, params, n_replicas=4, max_batch=N_REQUESTS, max_len=MAX_LEN,
        block_size=BLOCK, n_blocks=N_REQUESTS * -(-MAX_LEN // BLOCK),
        chunk_size=CHUNK)
    placed = []
    for i, rep in enumerate(cluster.replicas):
        on = {d for x in jax.tree.leaves((rep.params, rep.cache))
              for d in x.devices()}
        print(f"replica {i}: params and pool on {sorted(map(str, on))}")
        check(len(on) == 1, f"replica {i} spans {len(on)} devices")
        placed.append(on.pop())
    check(len(set(placed)) == 4 and set(placed) == set(devs),
          f"replicas share devices: {placed}")
    tokens = serve(cluster, prompts, vocab)
    agree = np.mean(np.asarray(tokens) == np.asarray(ref_tokens))
    print(f"router vs one-chip served tokens agreement: {agree:.3f}; "
          f"routed per replica {cluster.stats.routed}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and check on one chip; 4: the sharded "
                         "replica and the 4-replica router only")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache(ROOT)
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        fail(f"JAX found platform {platform!r} ({len(devs)} device(s)), "
             "not a TPU; this smoke runs only on the chip")
    if len(devs) != args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devs)} device(s)")
    kind = devs[0].device_kind
    print(f"device: {platform} {kind} x {len(devs)}; compile cache "
          f"{cache_dir}")
    compiles = CompileWatch()

    from repro.configs import get_config
    from repro.models.zoo import build_model
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=KEEP_LAYERS, use_pallas=True)
    print(f"model: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} kv x {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); cut: "
          f"{KEEP_LAYERS} of {full.n_layers} layers kept")
    model = build_model(cfg)
    params = init_params(model)
    if args.chips == 1:
        one_chip(model, params)
    else:
        four_chips(model, params)
    print(f"compile seconds {compiles.seconds:.3f}, persistent-cache "
          f"hits {compiles.cache_hits} (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
