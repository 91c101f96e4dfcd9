#!/usr/bin/env python3
"""Size a cell's KV pool without a chip: compile its two step programs,
the fused decode at ``max_batch`` rows and one fused prefill chunk, for
a described TPU v5e, and print one JSON line per program with what
``memory_analysis()`` says, beside the chip's memory.

    python3 bench/memory.py --workload internlm2-20b.chat

The programs are the engine's own (``paged_decode_fn``,
``paged_chunk_fn`` over the model's ``decode_step``, the pool donated),
at the cell's configuration and engine sizes, ``n_blocks`` included.
``--n-blocks`` tries another pool.  Each line gives the margin the
program leaves of the memory a v5e gives its programs (15.75 GiB), after
a reserve for the runtime.  The pool costs about twice its size: the
layer scan holds a second copy.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HBM_BYTES = 63 * 2**28      # 15.75 GiB a v5e gives its programs
RESERVE_BYTES = 2**28       # left to the runtime: 0.25 GiB


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--n-blocks", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import program, spec
    from repro.serve.engine import paged_chunk_fn, paged_decode_fn

    cell = spec.load_cell(args.workload)
    eng = cell.engine
    n_blocks = args.n_blocks or eng["n_blocks"]
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(dev)
    jax.default_backend = lambda: "tpu"     # trace the chip's path
    model = program.build(cell.config, cell.reference)
    B, C, bs = eng["max_batch"], eng["chunk_size"], eng["block_size"]
    NB = -(-eng["max_len"] // bs)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = placed(jax.eval_shape(
        lambda: model.init_paged_cache(n_blocks, bs)))
    step = model.decode_step
    programs = {
        "decode": jax.jit(paged_decode_fn(step), donate_argnums=(1,)).lower(
            params, pool, i32(B), i32(B), i32(B, NB)),
        "chunk": jax.jit(paged_chunk_fn(step), donate_argnums=(1, 5)).lower(
            params, pool, i32(1, C), i32(1), i32(1, NB), i32(B), i32(),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=one)),
    }
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    for name, lowered in programs.items():
        m = lowered.compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({
            "program": name, "rows": B if name == "decode" else 1,
            "n_blocks": n_blocks, "pool_bytes": pool_bytes,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "total_bytes": total, "reserve_bytes": RESERVE_BYTES,
            "hbm_bytes": HBM_BYTES,
            "margin_bytes": HBM_BYTES - RESERVE_BYTES - total}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
