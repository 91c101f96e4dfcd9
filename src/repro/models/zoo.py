"""Unified model API over all assigned architectures.

``build_model(cfg)`` returns a ``Model`` whose members are pure functions
(ready for jax.jit / .lower()):

  init(key)                         -> params
  param_specs()                     -> PartitionSpec pytree (mirrors params)
  loss(params, batch)               -> (scalar loss, aux dict)
  prefill(params, batch, max_len)   -> (last_logits, cache)
  decode(params, cache, tokens,pos) -> (logits, new cache)
  decode_step(params, cache, tokens, pos) -> (next_tokens, new cache[,
                                               expert counts (moe)])
  init_cache(batch, max_len)        -> decode cache
  cache_specs(batch_axes, seq_axis) -> PartitionSpec pytree for the cache
  input_specs(cell)                 -> ShapeDtypeStructs for a shape cell
  input_shardings(cell, batch_axes) -> PartitionSpecs for those inputs
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelCfg, ShapeCell
from repro.models import encdec as encdec_mod
from repro.models import transformer as lm_mod


def cross_entropy(logits, labels, mask=None):
    """logits [B,S,V] (f32), labels [B,S] -> mean nll over unmasked tokens."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# how many stub patch embeddings each shape cell gets for the VLM arch
_VLM_PATCHES = {"train_4k": 576, "prefill_32k": 2880, "decode_32k": 2880,
                "long_500k": 2880}


def fused_decode_step(decode):
    """Build a ``decode_step`` from a ``decode``: greedy argmax over the
    last-position logit head (``transformer._last_pos_head``), fused so a
    jitted caller returns ``[B]`` int32 tokens and the ``[B, vocab]``
    logit matrix never crosses the step boundary.  THE one
    implementation — both model builders and the serving engines'
    fallback (for harness fakes that only define ``decode``) wrap it."""
    def decode_step(params, cache, tokens, pos, block_tables=None):
        logits, cache = decode(params, cache, tokens, pos, block_tables)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
    return decode_step


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelCfg
    init: Callable
    param_specs: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable
    cache_specs: Callable
    input_specs: Callable
    input_shardings: Callable
    # paged serving (None where the family has no paged KV cache):
    # init_paged_cache(n_blocks, block_size, mesh=None) -> pool (laid
    # out sharded when a serving mesh is passed); decode then takes
    # an optional block_tables=[B,NB] arg routing K/V through the pool
    init_paged_cache: Optional[Callable] = None
    # the fused decode hot path: greedy sampling (argmax over the
    # last-position logit head) runs INSIDE the step, so a jitted/AOT
    # caller moves only [B] int32 tokens across the host boundary
    # instead of [B, vocab] logits.  Same signature as ``decode`` but
    # returns (next_tokens [B] int32, new_cache); an MoE model's adds a
    # third output, the launch's expert counts (int32 scalars by name)
    decode_step: Optional[Callable] = None


def _frontend_width(cfg: ModelCfg, cell: ShapeCell) -> int:
    if cfg.frontend == "vision":
        return _VLM_PATCHES[cell.name]
    return 0


def build_model(cfg: ModelCfg) -> Model:
    if cfg.encdec is not None:
        return _build_encdec(cfg)
    return _build_lm(cfg)


# ---------------------------------------------------------------------------
# decoder-only LMs (dense / moe / hybrid / ssm / vlm)


def _build_lm(cfg: ModelCfg) -> Model:
    def init(key):
        return lm_mod.init_lm(key, cfg)

    def param_specs():
        return lm_mod.lm_specs(cfg)

    def loss(params, batch):
        logits, aux, _ = lm_mod.lm_apply(
            params, cfg, tokens=batch["tokens"], mode="train",
            prefix_embeds=batch.get("prefix_embeds"))
        l = cross_entropy(logits, batch["labels"])
        if cfg.moe:
            l = (l + cfg.moe.router_aux_coef * aux["moe_load_balance"]
                 + cfg.moe.router_z_coef * aux["moe_router_z"])
        aux = dict(aux, ce=l)
        return l, aux

    def prefill(params, batch, max_len=None):
        logits, _, cache = lm_mod.lm_apply(
            params, cfg, tokens=batch["tokens"], mode="prefill",
            prefix_embeds=batch.get("prefix_embeds"), max_len=max_len)
        return logits[:, -1, :], cache

    def decode(params, cache, tokens, pos, block_tables=None):
        logits, _, cache = lm_mod.lm_apply(
            params, cfg, tokens=tokens, mode="decode", cache=cache,
            write_pos=pos, block_tables=block_tables)
        return logits[:, -1, :], cache

    def init_cache(batch, max_len):
        return lm_mod.init_decode_cache(cfg, batch, max_len)

    def init_paged_cache(n_blocks, block_size, mesh=None):
        return lm_mod.init_paged_decode_cache(cfg, n_blocks, block_size,
                                              mesh=mesh)

    def cache_specs(batch_axes=("data",), seq_axis="model"):
        return lm_mod.decode_cache_specs(cfg, batch_axes, seq_axis)

    def input_specs(cell: ShapeCell):
        B, S = cell.global_batch, cell.seq_len
        pfx = _frontend_width(cfg, cell)
        tok = lambda s: jax.ShapeDtypeStruct((B, s), jnp.int32)
        if cell.kind == "train":
            out = {"tokens": tok(S), "labels": tok(S)}
            if pfx:
                out["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (B, pfx, cfg.d_model), jnp.bfloat16)
            return out
        if cell.kind == "prefill":
            out = {"tokens": tok(S)}
            if pfx:
                out["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (B, pfx, cfg.d_model), jnp.bfloat16)
            return out
        # decode: one new token against a seq_len cache
        cache = jax.eval_shape(lambda: init_cache(B, S))
        return {"tokens": tok(1), "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
                "cache": cache}

    def input_shardings(cell: ShapeCell, batch_axes=("data",),
                        seq_axis="model"):
        bspec = P(batch_axes, None)
        if cell.kind == "train":
            out = {"tokens": bspec, "labels": bspec}
            if _frontend_width(cfg, cell):
                out["prefix_embeds"] = P(batch_axes, None, None)
            return out
        if cell.kind == "prefill":
            out = {"tokens": bspec}
            if _frontend_width(cfg, cell):
                out["prefix_embeds"] = P(batch_axes, None, None)
            return out
        return {"tokens": bspec, "pos": P(batch_axes),
                "cache": cache_specs(batch_axes, seq_axis)}

    decode_step = fused_decode_step(decode)
    if cfg.moe is not None:
        def decode_step(params, cache, tokens, pos, block_tables=None):
            """The fused step, and the launch's expert-layer counts as a
            third output (``transformer.expert_counts``)."""
            logits, aux, cache = lm_mod.lm_apply(
                params, cfg, tokens=tokens, mode="decode", cache=cache,
                write_pos=pos, block_tables=block_tables)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt, cache, lm_mod.expert_counts(aux)

    return Model(cfg, init, param_specs, loss, prefill, decode, init_cache,
                 cache_specs, input_specs, input_shardings,
                 init_paged_cache=init_paged_cache, decode_step=decode_step)


# ---------------------------------------------------------------------------
# encoder-decoder (seamless)


def _build_encdec(cfg: ModelCfg) -> Model:
    def init(key):
        return encdec_mod.init_encdec(key, cfg)

    def param_specs():
        return encdec_mod.encdec_specs(cfg)

    def loss(params, batch):
        logits, aux, _ = encdec_mod.encdec_apply(
            params, cfg, tokens=batch["tokens"], frames=batch["frames"],
            mode="train")
        l = cross_entropy(logits, batch["labels"])
        return l, dict(aux, ce=l)

    def prefill(params, batch, max_len=None):
        logits, _, cache = encdec_mod.encdec_apply(
            params, cfg, tokens=batch["tokens"], frames=batch["frames"],
            mode="prefill", max_len=max_len)
        return logits[:, -1, :], cache

    def decode(params, cache, tokens, pos, block_tables=None):
        if block_tables is not None:
            raise NotImplementedError("no paged decode for encoder-decoder")
        logits, _, cache = encdec_mod.encdec_apply(
            params, cfg, tokens=tokens, mode="decode", cache=cache,
            write_pos=pos)
        return logits[:, -1, :], cache

    def init_cache(batch, max_len, enc_len=None):
        return encdec_mod.init_encdec_cache(cfg, batch, max_len,
                                            enc_len or max_len)

    def cache_specs(batch_axes=("data",), seq_axis="model"):
        return encdec_mod.encdec_cache_specs(batch_axes, seq_axis)

    def input_specs(cell: ShapeCell):
        B, S = cell.global_batch, cell.seq_len
        tok = lambda s: jax.ShapeDtypeStruct((B, s), jnp.int32)
        frames = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        if cell.kind == "train":
            return {"frames": frames, "tokens": tok(S), "labels": tok(S)}
        if cell.kind == "prefill":
            return {"frames": frames, "tokens": tok(S)}
        cache = jax.eval_shape(lambda: init_cache(B, S, S))
        return {"tokens": tok(1), "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
                "cache": cache}

    def input_shardings(cell: ShapeCell, batch_axes=("data",),
                        seq_axis="model"):
        bspec = P(batch_axes, None)
        fspec = P(batch_axes, None, None)
        if cell.kind == "train":
            return {"frames": fspec, "tokens": bspec, "labels": bspec}
        if cell.kind == "prefill":
            return {"frames": fspec, "tokens": bspec}
        return {"tokens": bspec, "pos": P(batch_axes),
                "cache": cache_specs(batch_axes, seq_axis)}

    return Model(cfg, init, param_specs, loss, prefill, decode, init_cache,
                 cache_specs, input_specs, input_shardings,
                 decode_step=fused_decode_step(decode))


def count_params(cfg: ModelCfg) -> int:
    """Total parameter count (from shapes only, no allocation)."""
    import math
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def count_active_params(cfg: ModelCfg) -> int:
    """Active-per-token parameter count (MoE: routed experts scaled by k/E)."""
    total = count_params(cfg)
    if not cfg.moe:
        return total
    m = cfg.moe
    expert_p = 3 * cfg.d_model * m.d_ff_expert
    n_moe_layers = cfg.n_layers - m.first_k_dense
    routed = n_moe_layers * m.n_experts * expert_p
    active_routed = n_moe_layers * m.top_k * expert_p
    return total - routed + active_routed
