"""Serving launcher: --arch <id>, engine over the production mesh (dry-run)
or a reduced config executed locally.

  python -m repro.launch.serve --arch deepseek-v2-236b --dry-run --cell decode_32k
  python -m repro.launch.serve --arch gemma3-1b --host --requests 8
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", default="decode_32k")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--host", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV engine "
                         "(block-pool cache + chunked prefill)")
    ap.add_argument("--block-size", type=int, default=8)
    args = ap.parse_args()

    if args.dry_run:
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count"
                                     "=512").strip()
        from repro.launch.dryrun import run_cell
        run_cell(args.arch, args.cell, args.multi_pod)
        return

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models.zoo import build_model
    from repro.serve.engine import PagedServingEngine, ServingEngine

    cfg = reduced(get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.paged:
        eng = PagedServingEngine(model, params, max_batch=4, max_len=64,
                                 block_size=args.block_size, chunk_size=8)
    else:
        eng = ServingEngine(model, params, max_batch=4, max_len=64)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
                   max_new_tokens=8)
    stats = eng.run_until_done()
    extra = (f", {stats.prefill_chunks} chunks, "
             f"{stats.preemptions} preemptions" if args.paged else "")
    print(f"served {stats.completed} requests, "
          f"{stats.decoded_tokens} tokens{extra}")


if __name__ == "__main__":
    main()
