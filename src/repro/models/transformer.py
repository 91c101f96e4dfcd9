"""Decoder-only LM trunk: scan-over-layers, uniform across families.

One layer body serves dense / moe / hybrid(attn+mamba) / ssm(rwkv6) / vlm
configs; per-layer variation (local vs global attention) is DATA (a scanned
bool), not structure, so the stacked-parameter scan stays uniform and the HLO
(and compile time for the 512-device dry-run) stays small.  DeepSeek-V2's
leading dense-FFN layer(s) sit outside the scanned stack.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import attention as attn_mod
from repro.models.layers import basic, mamba as mamba_mod, mla as mla_mod
from repro.models.layers import moe as moe_mod, rwkv as rwkv_mod
from repro.sharding import ctx


# ---------------------------------------------------------------------------
# per-layer init/specs


def _init_layer(key, cfg, moe_layer: bool):
    ks = jax.random.split(key, 8)
    fam = cfg.family
    if fam == "ssm":  # rwkv6
        return {
            "ln1": basic.init_layernorm(cfg.d_model),
            "tmix": rwkv_mod.init_rwkv_tmix(ks[0], cfg),
            "ln2": basic.init_layernorm(cfg.d_model),
            "cmix": rwkv_mod.init_rwkv_cmix(ks[1], cfg),
        }
    p = {"ln1": basic.init_rmsnorm(cfg.d_model),
         "ln2": basic.init_rmsnorm(cfg.d_model)}
    if cfg.attn_impl == "mla":
        p["attn"] = mla_mod.init_mla(ks[0], cfg)
    else:
        p["attn"] = attn_mod.init_attention(ks[0], cfg)
    if fam == "hybrid":
        p["mamba"] = mamba_mod.init_mamba(ks[1], cfg)
        p["norm_attn"] = basic.init_rmsnorm(cfg.n_heads * cfg.head_dim)
        p["norm_ssm"] = basic.init_rmsnorm(cfg.d_model)
    if moe_layer:
        p["ffn"] = moe_mod.init_moe(ks[2], cfg)
    else:
        p["ffn"] = basic.init_mlp(ks[2], cfg.d_model, cfg.d_ff, gated=True)
    if cfg.post_norms:
        p["post_ln1"] = basic.init_rmsnorm(cfg.d_model)
        p["post_ln2"] = basic.init_rmsnorm(cfg.d_model)
    return p


def _layer_specs(cfg, moe_layer: bool):
    fam = cfg.family
    if fam == "ssm":
        return {
            "ln1": basic.layernorm_specs(),
            "tmix": rwkv_mod.rwkv_tmix_specs(cfg),
            "ln2": basic.layernorm_specs(),
            "cmix": rwkv_mod.rwkv_cmix_specs(cfg),
        }
    s = {"ln1": basic.rmsnorm_specs(), "ln2": basic.rmsnorm_specs()}
    if cfg.attn_impl == "mla":
        s["attn"] = mla_mod.mla_specs(cfg)
    else:
        s["attn"] = attn_mod.attention_specs(cfg)
    if fam == "hybrid":
        s["mamba"] = mamba_mod.mamba_specs(cfg)
        s["norm_attn"] = basic.rmsnorm_specs()
        s["norm_ssm"] = basic.rmsnorm_specs()
    if moe_layer:
        s["ffn"] = moe_mod.moe_specs(cfg)
    else:
        s["ffn"] = basic.mlp_specs(gated=True)
    if cfg.post_norms:
        s["post_ln1"] = basic.rmsnorm_specs()
        s["post_ln2"] = basic.rmsnorm_specs()
    return s


def _n_pre_layers(cfg) -> int:
    return cfg.moe.first_k_dense if cfg.moe else 0


def _norm(cfg):
    return basic.layernorm if cfg.family == "ssm" else basic.rmsnorm


# ---------------------------------------------------------------------------
# whole-model init/specs


def init_lm(key, cfg):
    ks = jax.random.split(key, cfg.n_layers + 4)
    n_pre = _n_pre_layers(cfg)
    p = {
        "embed": basic.init_embed(ks[0], cfg.vocab_size, cfg.d_model,
                                  cfg.tie_embeddings),
        "ln_f": (basic.init_layernorm(cfg.d_model) if cfg.family == "ssm"
                 else basic.init_rmsnorm(cfg.d_model)),
    }
    if cfg.family == "ssm":
        p["ln0"] = basic.init_layernorm(cfg.d_model)   # rwkv embeds norm
    if cfg.meta_tokens:
        p["meta"] = jax.random.normal(ks[1], (cfg.meta_tokens, cfg.d_model),
                                      jnp.float32) * 0.02
    if cfg.frontend == "vision":
        p["mm_proj"] = jax.random.normal(
            ks[2], (cfg.d_model, cfg.d_model), jnp.float32) * cfg.d_model ** -0.5
    moe_layer = cfg.moe is not None
    p["pre_layers"] = [
        _init_layer(ks[3 + i], cfg, moe_layer=False) for i in range(n_pre)]
    stack_keys = jnp.stack(
        [ks[3 + n_pre + i] for i in range(cfg.n_layers - n_pre)])
    p["layers"] = jax.vmap(
        functools.partial(_init_layer, cfg=cfg, moe_layer=moe_layer)
    )(stack_keys)
    return p


def lm_specs(cfg):
    n_pre = _n_pre_layers(cfg)
    s = {
        "embed": basic.embed_specs(cfg.tie_embeddings),
        "ln_f": (basic.layernorm_specs() if cfg.family == "ssm"
                 else basic.rmsnorm_specs()),
    }
    if cfg.family == "ssm":
        s["ln0"] = basic.layernorm_specs()
    if cfg.meta_tokens:
        s["meta"] = P(None, None)
    if cfg.frontend == "vision":
        s["mm_proj"] = P("data", "model")
    s["pre_layers"] = [_layer_specs(cfg, moe_layer=False) for _ in range(n_pre)]
    # stacked layers: same specs with a leading (unsharded) layer axis
    per = _layer_specs(cfg, moe_layer=cfg.moe is not None)
    s["layers"] = jax.tree.map(lambda sp: P(None, *sp), per,
                               is_leaf=lambda x: isinstance(x, P))
    return s


# ---------------------------------------------------------------------------
# decode cache


def init_decode_cache(cfg, batch, max_len):
    L = cfg.n_layers
    c = {}
    if cfg.attn_impl == "mla":
        c.update(mla_mod.init_mla_cache(cfg, batch, max_len, L))
    elif cfg.attn_impl == "gqa":
        c.update(attn_mod.init_kv_cache(cfg, batch, max_len, L))
    if cfg.family == "hybrid":
        c.update(mamba_mod.init_mamba_state(cfg, batch, L))
    if cfg.family == "ssm":
        c.update(rwkv_mod.init_rwkv_state(cfg, batch, L))
    return c


def init_paged_decode_cache(cfg, n_blocks, block_size, mesh=None):
    """The paged decode cache: one shared pool of KV blocks per layer.

    Only plain GQA-attention stacks page cleanly — recurrent families
    (ssm/rwkv/hybrid) carry per-slot state that is not positional, and
    meta tokens / modality prefixes are prepended by prefill-mode calls
    the chunked path never makes — so everything else raises loudly.

    ``mesh``: lay the pool out sharded at birth (KV heads over the
    mesh's ``'model'`` axis — ``attention.paged_pool_spec``) for a
    replica that decodes over multiple chips."""
    if (cfg.attn_impl != "gqa" or cfg.family in ("ssm", "hybrid")
            or cfg.ssm is not None or cfg.rwkv is not None
            or cfg.meta_tokens or cfg.frontend is not None):
        raise NotImplementedError(
            f"{cfg.name}: paged KV cache needs a plain GQA attention "
            "stack (no recurrent state, meta tokens, or prefix embeds)")
    return attn_mod.init_paged_kv_cache(cfg, n_blocks, block_size,
                                        cfg.n_layers, mesh=mesh)


def decode_cache_specs(cfg, batch_axes=("data",), seq_axis="model"):
    s = {}
    if cfg.attn_impl == "mla":
        s.update(mla_mod.mla_cache_specs(batch_axes, seq_axis))
    elif cfg.attn_impl == "gqa":
        s.update(attn_mod.kv_cache_specs(batch_axes, seq_axis))
    if cfg.family == "hybrid":
        s.update(mamba_mod.mamba_state_specs(batch_axes))
    if cfg.family == "ssm":
        s.update(rwkv_mod.rwkv_state_specs(batch_axes))
    return s


def _split_cache(cache, kind):
    """Split a stacked cache dict into (attn_part, state_part) per kind."""
    attn_keys = {"k", "v", "ckv", "krope"}
    a = {k: v for k, v in cache.items() if k in attn_keys} if cache else None
    st = {k: v for k, v in cache.items() if k not in attn_keys} if cache else None
    return (a or None), (st or None)


# ---------------------------------------------------------------------------
# one decoder layer


def _aux_zeros(cfg, mode):
    """A layer's aux values before it runs: the router losses, and for
    a serving MoE the expert layer's counts (``expert_counts``)."""
    aux = {"moe_load_balance": jnp.zeros((), jnp.float32),
           "moe_router_z": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None and mode != "train":
        aux.update({moe_mod.EXPERT_PAIRS: jnp.zeros((), jnp.int32),
                    moe_mod.EXPERT_MAX: jnp.zeros((), jnp.int32)})
    return aux


def _merge_aux(a, b):
    return {k: (jnp.maximum if k == moe_mod.EXPERT_MAX else jnp.add)(
        a[k], b[k]) for k in a}


def expert_counts(aux) -> dict:
    """The launch's expert-layer counts out of ``lm_apply``'s aux, summed
    over layers (the most pairs one expert got: the largest in any
    layer); empty for a model without experts."""
    return {k: aux[k] for k in (moe_mod.EXPERT_PAIRS, moe_mod.EXPERT_MAX)
            if k in aux}


def _layer(x, lp, *, cfg, positions, is_global, cache_layer, write_pos, mode,
           block_tables=None):
    """Returns (x, new_cache_layer, aux)."""
    cdt = x.dtype
    x = ctx.constrain(x, "batch", None, None)
    aux = _aux_zeros(cfg, mode)
    norm = _norm(cfg)

    if cfg.family == "ssm":
        tm_state = None
        if mode == "decode":
            tm_state = {"shift": cache_layer["tm_shift"],
                        "wkv": cache_layer["wkv"]}
        h, tm_new = rwkv_mod.rwkv_time_mix(
            lp["tmix"], basic.layernorm(lp["ln1"], x), cfg, tm_state,
            need_state=(mode != "train"))
        x = x + h
        cm_state = cache_layer["cm_shift"] if mode == "decode" else None
        h, cm_new = rwkv_mod.rwkv_channel_mix(
            lp["cmix"], basic.layernorm(lp["ln2"], x), cfg, cm_state)
        x = x + h
        new_cache = {"tm_shift": tm_new["shift"], "wkv": tm_new["wkv"],
                     "cm_shift": cm_new}
        return x, new_cache, aux

    h_in = norm(lp["ln1"], x, cfg.norm_eps)
    attn_cache, state_cache = _split_cache(cache_layer, cfg.family)
    use_cache = attn_cache if mode == "decode" else None

    if cfg.attn_impl == "mla":
        a_out, a_cache = mla_mod.mla_attention(
            lp["attn"], h_in, cfg=cfg, positions=positions,
            cache=use_cache, write_pos=write_pos)
    else:
        a_out, a_cache = attn_mod.attention(
            lp["attn"], h_in, cfg=cfg, positions=positions,
            is_global=is_global, cache=use_cache, write_pos=write_pos,
            block_tables=block_tables,
            pre_output=(cfg.family == "hybrid"))

    new_cache = {}
    if cfg.family == "hybrid":
        m_state = state_cache if mode == "decode" else None
        if m_state is not None:
            m_state = {"conv": m_state["conv"], "h": m_state["h"]}
        s_out, s_new = mamba_mod.mamba_mixer(lp["mamba"], h_in, cfg, m_state,
                                             need_state=(mode != "train"))
        # padded dead heads are zero; slice back to the real width so the
        # parallel SSM path (d_inner == n_heads*head_dim) fuses exactly
        real = cfg.n_heads * cfg.head_dim
        a_pre = a_out[..., :real]
        fused = 0.5 * (basic.rmsnorm(lp["norm_attn"], a_pre, cfg.norm_eps)
                       + basic.rmsnorm(lp["norm_ssm"], s_out, cfg.norm_eps))
        wo = lp["attn"]["wo"].astype(cdt)[:cfg.n_heads].reshape(
            real, cfg.d_model)
        a_out = jnp.einsum("bsz,zd->bsd", fused, wo)
        new_cache.update({"conv": s_new["conv"], "h": s_new["h"]})

    if cfg.post_norms:
        a_out = norm(lp["post_ln1"], a_out, cfg.norm_eps)
    if cfg.remat_policy == "save_attn":
        # tag the attention output so the remat policy can keep it: the
        # backward pass then skips recomputing the whole attention block
        from jax.ad_checkpoint import checkpoint_name
        a_out = checkpoint_name(a_out, "attn_out")
    x = x + a_out

    h_in = norm(lp["ln2"], x, cfg.norm_eps)
    if "router" in lp["ffn"] and mode != "train":
        # serving: every token of the launch (all rows of a decode, all
        # tokens of a chunk) in one dropless call over the held experts
        with jax.named_scope("moe"):
            f_out, counts = moe_mod.expert_layer(lp["ffn"], h_in, cfg,
                                                 valid=positions >= 0)
        aux.update(counts)
    elif "router" in lp["ffn"]:
        moe_fn = (moe_mod.moe_ffn_sharded if cfg.moe_impl == "shard"
                  else moe_mod.moe_ffn)
        f_out, moe_aux = moe_fn(lp["ffn"], h_in, cfg)
        aux.update(moe_aux)
    else:
        f_out = basic.mlp(lp["ffn"], h_in, cfg.act)
    if cfg.post_norms:
        f_out = norm(lp["post_ln2"], f_out, cfg.norm_eps)
    x = x + f_out

    if mode != "train" and a_cache is not None:
        new_cache.update(a_cache)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# trunk drivers


def _prefill_pad_cache(cache_layer, max_len):
    """Pad per-layer [B,S,...] attention caches up to max_len slots and cast
    to the cache storage dtype (bf16)."""
    def pad(c):
        c = c.astype(jnp.bfloat16)
        S = c.shape[1]
        if S == max_len:
            return c
        pads = [(0, 0)] * c.ndim
        pads[1] = (0, max_len - S)
        return jnp.pad(c, pads)
    return {k: (pad(v) if k in ("k", "v", "ckv", "krope") else v)
            for k, v in cache_layer.items()}


def _last_pos_head(x, mode):
    """The last-position logit head: every non-train call (prefill, decode,
    chunked-prefill-through-decode) unembeds ONLY the final position.

    This slice is the contract the fused serving hot path builds on: with
    the trunk output reduced to ``[B, 1, D]`` before the unembed, a fused
    ``decode_step`` (``models.zoo``) can argmax ``[B, 1, V] -> [B]``
    entirely on device and a serving engine moves 4 bytes per sequence
    across the host boundary instead of a ``[B, V]`` logit row."""
    if mode != "train" and x.shape[1] > 1:
        return x[:, -1:, :]
    return x


def lm_apply(params, cfg, *, tokens, mode, prefix_embeds=None, cache=None,
             write_pos=None, block_tables=None, max_len=None, remat=True):
    """Run the LM trunk.

    tokens        [B,S] int32 (decode: S==1, or a chunked-prefill chunk)
    prefix_embeds [B,P,D] stub modality embeddings (vlm), prepended
    cache         stacked decode cache (mode == 'decode'); with
                  block_tables, the stacked PAGED pool [L,n_blocks,bs,...]
    write_pos     [B] cache slot for the new tokens (decode); may be
                  negative for left-padded chunked-prefill rows (those
                  writes are dropped by the paged scatter)
    block_tables  [B,NB] paged decode: per-row physical block ids
    Returns (logits, aux, new_cache).
    """
    if block_tables is not None and cfg.attn_impl != "gqa":
        raise NotImplementedError(
            f"paged decode needs a GQA KV cache, not {cfg.attn_impl}")
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    x = basic.embed_tokens(params["embed"], tokens, cdt,
                           scale_by_dim=cfg.scale_embeds)
    if cfg.family == "ssm":
        x = basic.layernorm(params["ln0"], x)

    n_prefix = 0
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(cdt)
        if "mm_proj" in params:
            pe = jnp.einsum("bpd,de->bpe", pe, params["mm_proj"].astype(cdt))
        x = jnp.concatenate([pe, x], axis=1)
        n_prefix += pe.shape[1]
    if cfg.meta_tokens and mode != "decode":
        meta = jnp.broadcast_to(params["meta"].astype(cdt),
                                (B, cfg.meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta, x], axis=1)
        n_prefix += cfg.meta_tokens

    St = x.shape[1]
    if mode == "decode":
        # decode calls may carry St > 1 tokens (chunked prefill through
        # the decode path); token t sits at absolute position
        # write_pos + t.  For St == 1 this is the old write_pos[:, None].
        positions = write_pos[:, None] + jnp.arange(St, dtype=jnp.int32)[None]
    else:
        positions = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32)[None], (B, St))

    n_pre = _n_pre_layers(cfg)
    glob = jnp.asarray(cfg.global_layer_mask(), bool)
    aux_tot = _aux_zeros(cfg, mode)
    max_len = max_len or St
    pre_caches = []

    # --- leading unstacked layers (deepseek dense layer 0) -------------------
    for i, lp in enumerate(params["pre_layers"]):
        cl = (jax.tree.map(lambda c: c[i], cache) if cache is not None else None)
        x, ncl, aux = _layer(x, lp, cfg=cfg, positions=positions,
                             is_global=glob[i], cache_layer=cl,
                             write_pos=write_pos, mode=mode,
                             block_tables=block_tables)
        aux_tot = _merge_aux(aux_tot, aux)
        if mode != "train":
            pre_caches.append(_prefill_pad_cache(ncl, max_len)
                              if mode == "prefill" else ncl)

    # --- scanned stack --------------------------------------------------------
    stack = params["layers"]
    glob_stack = glob[n_pre:]
    cache_stack = (jax.tree.map(lambda c: c[n_pre:], cache)
                   if cache is not None else None)

    def body(carry, xs):
        x, aux_acc = carry
        if mode == "decode":
            lp, g, cl = xs
        else:
            lp, g = xs
            cl = None
        x, ncl, aux = _layer(x, lp, cfg=cfg, positions=positions,
                             is_global=g, cache_layer=cl,
                             write_pos=write_pos, mode=mode,
                             block_tables=block_tables)
        aux_acc = _merge_aux(aux_acc, aux)
        if mode == "train":
            ys = 0.0
        elif mode == "prefill":
            ys = _prefill_pad_cache(ncl, max_len)
        else:
            ys = ncl
        return (x, aux_acc), ys

    if mode == "train" and remat:
        policy = (jax.checkpoint_policies.save_only_these_names("attn_out")
                  if cfg.remat_policy == "save_attn"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    xs = ((stack, glob_stack, cache_stack) if mode == "decode"
          else (stack, glob_stack))
    (x, aux_tot), ys = jax.lax.scan(body, (x, aux_tot), xs)

    x = (basic.layernorm if cfg.family == "ssm" else basic.rmsnorm)(
        params["ln_f"], x, cfg.norm_eps)
    if n_prefix and mode != "decode":
        x = x[:, n_prefix:, :]
    x = _last_pos_head(x, mode)
    logits = basic.unembed(params["embed"], x, cdt, cfg.logit_softcap,
                           vocab=cfg.vocab_size)

    new_cache = None
    if mode != "train":
        new_cache = ys
        if pre_caches:
            pre_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pre_caches)
            new_cache = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                pre_stacked, new_cache)
    return logits, aux_tot, new_cache
