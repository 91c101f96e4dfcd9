"""Compile the serving main path for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, DMA slices across a tiled axis, programs larger
than the chip's memory, kernels GSPMD cannot partition.  These tests
compile, at internlm2-20b's published widths, the paged-attention
kernel in both lowerings and the jitted paged decode step at
``chip_smoke.py``'s depth, one chip and a (data=1, model=4) mesh.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import dataclasses
import importlib.util
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.models.zoo import build_model
from repro.serve.engine import paged_chunk_fn, paged_decode_fn
from repro.sharding.plans import (named_tree, paged_decode_shardings,
                                  sanitize_specs, strip_axis)

V5E_HBM_BYTES = 16 * 2**30


def _smoke():
    """``chip_smoke.py``'s constants: the shapes these compiles guard."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _smoke()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Trace as the chip would: the model and kernel wrappers ask
    ``jax.default_backend()`` which path to take."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _smoke_model():
    cfg = dataclasses.replace(get_config(SMOKE.ARCH),
                              n_layers=SMOKE.KEEP_LAYERS, use_pallas=True)
    return build_model(cfg)


def _placed(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _cell_engine(cell="internlm2-20b.chat"):
    """A cell's engine: its decode batch, pool and table width."""
    path = (Path(__file__).resolve().parents[1] / "bench" / "workloads"
            / f"{cell}.json")
    return json.loads(path.read_text())["engine"]


CELL = _cell_engine()


@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("lowering", ["staged", "hbm", "hbm-cell"])
def test_paged_attention_compiles_for_v5e(one_chip, lowering, num_splits):
    """Both lowerings at internlm2-20b widths (48 heads over 8 KV heads
    of 128, bf16).  The staged form stages the whole pool into VMEM, so
    it gets a small pool; the HBM form gets the smoke's and the
    benchmark cells' pool, which the kernel reads where it lies: no
    temporary as large as the pool, so no copy or relayout of it."""
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_hbm)
    cfg = get_config(SMOKE.ARCH)
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if lowering == "hbm-cell":
        B, bs, P = CELL["max_batch"], CELL["block_size"], CELL["n_blocks"]
        NB = -(-CELL["max_len"] // bs)
    else:
        B, bs = SMOKE.N_REQUESTS, SMOKE.BLOCK
        NB = -(-SMOKE.MAX_LEN // bs)
        P = B * NB if lowering == "hbm" else 64
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = S((P, bs, KH, D), jnp.bfloat16)
    kern = paged_attention if lowering == "staged" else paged_attention_hbm
    compiled = jax.jit(lambda *a: kern(*a, num_splits=num_splits)).lower(
        S((B, H, D), jnp.bfloat16), pool, pool, S((B, NB), jnp.int32),
        S((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if lowering != "staged":
        pool_bytes = P * bs * KH * D * 2
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_paged_decode_step_compiles_for_v5e_and_fits(one_chip, on_tpu):
    """The engine's fused, donated decode step at the smoke's depth: the
    paged kernel is in its HLO (not the jnp gather), named after its
    jitted wrapper and under the ``paged_attention`` scope, and the
    program fits one chip's 16 GiB."""
    model = _smoke_model()
    B, NB = SMOKE.N_REQUESTS, -(-SMOKE.MAX_LEN // SMOKE.BLOCK)
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    pool = _placed(jax.eval_shape(
        lambda: model.init_paged_cache(B * NB, SMOKE.BLOCK)), one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(paged_decode_fn(model.decode_step),
                       donate_argnums=(1,)).lower(
        params, pool, i32(B), i32(B), i32(B, NB)).compile()
    kernels = [ln for ln in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    for ln in kernels:
        assert ln.strip().startswith("%_pa_jit")
        assert "/paged_attention/jit(_pa_jit)/pallas_call" in ln
    assert _total_bytes(compiled) <= V5E_HBM_BYTES


def test_sharded_paged_decode_step_compiles_for_v5e_2x2(topo, on_tpu):
    """The same step for a replica over a (data=1, model=4) mesh: the
    kernel runs per shard (``shard_map``) inside the partitioned step."""
    model = _smoke_model()
    B, NB = SMOKE.N_REQUESTS, -(-SMOKE.MAX_LEN // SMOKE.BLOCK)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    sh = paged_decode_shardings(model.cfg, mesh, B)
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    psh = named_tree(mesh, sanitize_specs(strip_axis(model.param_specs()),
                                          pshapes, mesh))
    params = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), pshapes, psh)
    pool_shapes = jax.eval_shape(
        lambda: model.init_paged_cache(B * NB, SMOKE.BLOCK))
    pool_sh = jax.tree.map(lambda _: sh["pool"], pool_shapes)
    pool = _placed(pool_shapes, sh["pool"])
    i32 = lambda s, shd: jax.ShapeDtypeStruct(s, jnp.int32, sharding=shd)  # noqa: E731
    step = jax.jit(paged_decode_fn(model.decode_step, mesh),
                   donate_argnums=(1,),
                   in_shardings=(psh, pool_sh, sh["batch"], sh["batch"],
                                 sh["repl"]),
                   out_shardings=(sh["io"], sh["batch"], pool_sh,
                                  sh["repl"]))
    compiled = step.lower(params, pool, i32((B,), sh["batch"]),
                          i32((B,), sh["batch"]),
                          i32((B, NB), sh["repl"])).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) <= V5E_HBM_BYTES


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_olmoe_steps_compile_for_v5e_and_fit(one_chip, on_tpu, program):
    """OLMoE-1B-7B as the ``olmoe-1b-7b.chat4k`` cell runs it: 4 layers,
    16 of 64 experts held, the cell's pool, its 64 decode rows or one
    256-token chunk.  The expert layer's grouped matmul lowers to the
    TPU's grouped-matmul kernels, and each program fits one chip."""
    cell = _cell_engine("olmoe-1b-7b.chat4k")
    base = get_config("olmoe-1b-7b")
    cfg = dataclasses.replace(
        base, n_layers=4, use_pallas=True,
        moe=dataclasses.replace(base.moe, expert_shards=4, expert_shard=0))
    model = build_model(cfg)
    B, C, bs = cell["max_batch"], cell["chunk_size"], cell["block_size"]
    NB = -(-cell["max_len"] // bs)
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    assert params["layers"]["ffn"]["w_gate"].shape == (4, 16, 2048, 1024)
    pool = _placed(jax.eval_shape(
        lambda: model.init_paged_cache(cell["n_blocks"], bs)), one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if program == "decode":
        lowered = jax.jit(paged_decode_fn(model.decode_step),
                          donate_argnums=(1,)).lower(
            params, pool, i32(B), i32(B), i32(B, NB))
    else:
        lowered = jax.jit(paged_chunk_fn(model.decode_step),
                          donate_argnums=(1, 5)).lower(
            params, pool, i32(1, C), i32(1), i32(1, NB), i32(B), i32(),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "%ragged-dot" in text
    if program == "decode":
        assert "%_pa_jit" in text
    assert _total_bytes(compiled) <= V5E_HBM_BYTES
