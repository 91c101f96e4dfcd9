"""GQA attention with memory-efficient (query-chunked) softmax.

Design notes
------------
* The score tensor is never materialized for the full (Sq, Skv) square: a
  ``lax.scan`` over query chunks bounds the transient to (chunk, Skv), which
  is the flash-attention memory behaviour expressed in pure jnp so the 512-way
  SPMD dry-run can lower it on any backend.  The Pallas TPU kernel
  (`repro.kernels.flash_attention`) is the hardware hot path.
* Sliding-window ("local") layers and full ("global") layers share one code
  path: the window is data (a mask term), not structure, so a scan over
  stacked layer params stays uniform.
* ``n_sink`` positions (hymba meta tokens) are always attendable even outside
  a local window.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.basic import apply_rope, rmsnorm, rope_tables
from repro.sharding import ctx

NEG_INF = -2.0e38


def init_attention(key, cfg):
    d, kh, hd = cfg.d_model, cfg.n_kv_heads, cfg.head_dim
    h = cfg.padded_heads
    k = jax.random.split(key, 4)
    lim = d ** -0.5
    p = {
        "wq": jax.random.uniform(k[0], (d, h, hd), jnp.float32, -lim, lim),
        "wk": jax.random.uniform(k[1], (d, kh, hd), jnp.float32, -lim, lim),
        "wv": jax.random.uniform(k[2], (d, kh, hd), jnp.float32, -lim, lim),
        "wo": jax.random.uniform(k[3], (h, hd, d), jnp.float32,
                                 -(h * hd) ** -0.5, (h * hd) ** -0.5),
    }
    if cfg.qk_norm:
        full = cfg.qk_norm_width == "full"
        p["q_norm"] = {"scale": jnp.zeros((h * hd if full else hd,),
                                          jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((kh * hd if full else hd,),
                                          jnp.float32)}
    return p


def attention_specs(cfg):
    s = {
        "wq": P("data", "model", None),
        "wk": P("data", "model", None),
        "wv": P("data", "model", None),
        "wo": P("model", None, "data"),
    }
    if cfg.qk_norm:
        s["q_norm"] = {"scale": P(None)}
        s["k_norm"] = {"scale": P(None)}
    return s


def _mask(qpos, kpos, *, causal, window, n_sink, is_global=True):
    """qpos [B,Sq], kpos [B,Skv] -> bool [B,Sq,Skv] (True = attendable).

    ``is_global`` may be a traced bool scalar (layers are scanned with the
    local/global pattern as data); when True the window term is disabled.
    """
    q = qpos[:, :, None]
    k = kpos[:, None, :]
    m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        m &= k <= q
    if window is not None:
        inside = (q - k) < window
        if n_sink:
            inside |= k < n_sink
        m &= inside | jnp.asarray(is_global, bool)
    m &= k >= 0  # kpos = -1 marks invalid (unwritten cache slots)
    return m


def _attend_chunk(q, k, v, qpos, kpos, *, scale, causal, window, n_sink, cap,
                  is_global, kv_map=None):
    """q [B,Cq,H,D], k/v [B,Skv,KH,D] -> [B,Cq,H,D]. Full-KV score per chunk."""
    B, Cq, H, D = q.shape
    KH = k.shape[2]
    if kv_map is not None and (KH != H or any(
            m != h // max(H // KH, 1) for h, m in enumerate(kv_map))):
        idx = jnp.asarray(kv_map, jnp.int32)
        k = jnp.take(k, idx, axis=2)
        v = jnp.take(v, idx, axis=2)
    elif KH != H:  # GQA: broadcast kv heads across query groups
        rep = H // KH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    m = _mask(qpos, kpos, causal=causal, window=window, n_sink=n_sink,
              is_global=is_global)
    s = jnp.where(m[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _pick_chunk(sq: int, chunk: int):
    """Choose (chunk_used, padded_len): prefer an exact divisor >= chunk/2,
    else pad sq up to a multiple of `chunk` (padded query rows are masked to
    uniform garbage and sliced off)."""
    if sq % chunk == 0:
        return chunk, sq
    for c in range(chunk, chunk // 2 - 1, -1):
        if sq % c == 0:
            return c, sq
    pad = ((sq + chunk - 1) // chunk) * chunk
    return chunk, pad


def attend(q, k, v, qpos, kpos, *, scale, causal=True, window=None, n_sink=0,
           cap=None, chunk=512, is_global=True, kv_map=None):
    """Query-chunked attention. q [B,Sq,H,D]; k,v [B,Skv,KH,D]."""
    B, Sq, H, D = q.shape
    if Sq <= chunk:
        return _attend_chunk(q, k, v, qpos, kpos, scale=scale, causal=causal,
                             window=window, n_sink=n_sink, cap=cap,
                             is_global=is_global, kv_map=kv_map)
    chunk, padded = _pick_chunk(Sq, chunk)
    if padded != Sq:
        q = jnp.pad(q, ((0, 0), (0, padded - Sq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, padded - Sq)),
                       constant_values=-(2 ** 30))
    n = padded // chunk
    qs = jnp.moveaxis(q.reshape(B, n, chunk, H, D), 1, 0)
    ps = jnp.moveaxis(qpos.reshape(B, n, chunk), 1, 0)

    # remat: the per-chunk scores/softmax are recomputed in the backward pass
    # instead of being stacked across chunks (which would materialize the full
    # (Sq, Skv) square the chunking exists to avoid).
    chunk_fn = jax.checkpoint(
        lambda qc, kk, vv, pc, kp, ig: _attend_chunk(
            qc, kk, vv, pc, kp, scale=scale, causal=causal, window=window,
            n_sink=n_sink, cap=cap, is_global=ig, kv_map=kv_map),
        policy=jax.checkpoint_policies.nothing_saveable)

    def body(_, qc_pc):
        qc, pc = qc_pc
        o = chunk_fn(qc, k, v, pc, kpos, jnp.asarray(is_global, bool))
        return (), o

    _, outs = jax.lax.scan(body, (), (qs, ps))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, padded, H, D)
    return out[:, :Sq]


def _qk_norm(p, x, cfg):
    """RMSNorm of projected q or k [B,S,heads,hd]: over each head's width
    (``qk_norm_width`` "head", Gemma 3), or over the whole projection
    before its split into heads ("full", OLMoE)."""
    if cfg.qk_norm_width == "head":
        return rmsnorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x.reshape(*x.shape[:2], -1), cfg.norm_eps
                   ).reshape(x.shape)


def _project_q(p, x, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = ctx.constrain(q, "batch", None, "model", None)
    if cfg.qk_norm:
        q = _qk_norm(p["q_norm"], q, cfg)
    return q


def _project_kv(p, xk, cfg):
    cdt = xk.dtype
    k = jnp.einsum("bsd,dhk->bshk", xk, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", xk, p["wv"].astype(cdt))
    k = ctx.constrain(k, "batch", None, "model", None)
    v = ctx.constrain(v, "batch", None, "model", None)
    if cfg.qk_norm:
        k = _qk_norm(p["k_norm"], k, cfg)
    return k, v


def _paged_update_gather(pool, k_new, v_new, block_tables, write_pos, layer):
    """Write ``Sq`` new tokens per row into one layer of the paged KV
    pool through the block table, then gather that layer's logical
    per-row K/V view for attention.

    pool          {'k','v': [L, n_blocks, bs, KH, hd]} the whole stacked
                  physical pool (a layer scan's carry)
    k_new, v_new  [B, Sq, KH, hd] projections for this call's tokens
    block_tables  [B, NB] int32 physical block per logical block (-1 =
                  unbacked; positions there are masked)
    write_pos     [B] first write position; may be NEGATIVE (left-padded
                  chunked-prefill calls, or inactive rows at -1) — those
                  token writes scatter out-of-bounds and are dropped
    layer         int32 scalar (may be traced): the layer written and read

    One scatter at ``[layer, block, slot]`` updates the pool in place and
    the gather reads ``[layer, page, slot]`` from it: no layer's pages
    are sliced out of the pool or written back.

    Returns (new_pool, k [B,S,KH,hd], v, kpos [B,S]) with S = NB*bs; the
    gathered view is the pure-jnp CPU reference of the paged decode (the
    Pallas ``kernels.paged_attention`` gathers page-by-page on TPU).
    """
    nb, bs = pool["k"].shape[1], pool["k"].shape[2]
    Sq = k_new.shape[1]
    NB = block_tables.shape[1]
    pos = write_pos[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(pos // bs, 0, NB - 1), axis=1)
    # physical block per new token; invalid -> nb, dropped by the
    # out-of-bounds scatter mode
    blk = jnp.where((pos >= 0) & (blk >= 0), blk, nb)
    write = lambda c, n: c.at[layer, blk, pos % bs].set(  # noqa: E731
        n.astype(c.dtype), mode="drop")
    new_pool = {"k": write(pool["k"], k_new), "v": write(pool["v"], v_new)}

    lslot = jnp.arange(NB * bs, dtype=jnp.int32)
    page = block_tables[:, lslot // bs]                     # [B, S]
    backed = page >= 0
    page = jnp.where(backed, page, 0)
    slot = jnp.where(backed, (lslot % bs)[None], 0)
    written = backed & (lslot[None] <= write_pos[:, None] + Sq - 1)
    k = new_pool["k"][layer, page, slot]
    v = new_pool["v"][layer, page, slot]
    kpos = jnp.where(written, lslot[None], -1)
    return new_pool, k, v, kpos


def attention(p, x, *, cfg, positions, is_global, theta=None,
              memory=None, mem_positions=None,
              cache: Optional[dict] = None, write_pos=None,
              block_tables=None, layer=None, pre_output=False, causal=True):
    """Unified attention layer.

    x          [B,Sq,D]   layer input (post-norm)
    positions  [B,Sq]     absolute positions of x tokens
    is_global  bool/array scalar flag; local layers use cfg.window
    memory     [B,Sm,D]   if set: cross-attention onto encoder memory
    cache      {'k','v' : [B,Smax,KH,hd]} decode/prefill KV cache (self-attn)
               — or the whole paged pool [L,n_blocks,bs,KH,hd] with
               block_tables, of which this call writes and reads ``layer``
    write_pos  [B]        decode: slot to write the new token's K/V
    block_tables [B,NB]   paged decode: per-row physical block ids; the
               cache is then the shared block pool and K/V are gathered
               through the table (``models`` CPU reference of the paged
               path; ``kernels.paged_attention`` is the TPU kernel)
    layer      int32 scalar (may be traced): the paged pool's layer
    pre_output if True return pre-wo head outputs [B,Sq,H*hd] (hymba fusion)

    Returns (out, new_cache).
    """
    cdt = x.dtype
    B, Sq, _ = x.shape
    hd = cfg.head_dim
    scale = hd ** -0.5
    theta = cfg.rope_theta if theta is None else theta
    cross = memory is not None

    q = _project_q(p, x, cfg)
    if not cross:
        sin_q, cos_q = rope_tables(positions, hd, theta)
        q = apply_rope(q, sin_q, cos_q)

    new_cache = None
    if cross:
        if cache is not None and "k" in cache:   # cached encoder projections
            k, v = cache["k"].astype(cdt), cache["v"].astype(cdt)
        else:
            k, v = _project_kv(p, memory, cfg)
        kpos = mem_positions
        causal = False
        new_cache = {"k": k, "v": v}
    elif cache is None:
        k_new, v_new = _project_kv(p, x, cfg)
        k = apply_rope(k_new, sin_q, cos_q)
        v = v_new
        kpos = positions
        new_cache = {"k": k, "v": v}   # prefill: rope'd K, raw V
    elif block_tables is not None:
        # paged decode: scatter the new K/V through the block table into
        # the shared pool, gather the logical context view back, and
        # attend with unwritten/unbacked slots masked (kpos = -1)
        k_new, v_new = _project_kv(p, x, cfg)
        k_new = apply_rope(k_new, sin_q, cos_q)
        new_cache, k, v, kpos = _paged_update_gather(
            cache, k_new, v_new, block_tables, write_pos, layer)
        k, v = k.astype(cdt), v.astype(cdt)
        causal = True
    else:
        # write new K/V into the cache at write_pos (per-row), then attend.
        k_new, v_new = _project_kv(p, x, cfg)
        k_new = apply_rope(k_new, sin_q, cos_q)

        if cfg.scatter_cache_update:
            # scatter keeps the (batch, seq)-sharded cache in place: the SPMD
            # partitioner masks updates shard-locally instead of re-gathering
            bi = jnp.arange(B, dtype=jnp.int32)[:, None]
            si = write_pos[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
            upd_b = lambda c, n: c.at[bi, si].set(n.astype(c.dtype),
                                                  mode="drop")
            k_cache = upd_b(cache["k"], k_new)
            v_cache = upd_b(cache["v"], v_new)
        else:
            def upd(c, n, wp):
                return jax.lax.dynamic_update_slice(c, n.astype(c.dtype),
                                                    (wp, 0, 0))
            k_cache = jax.vmap(upd)(cache["k"], k_new, write_pos)
            v_cache = jax.vmap(upd)(cache["v"], v_new, write_pos)
        k_cache = ctx.constrain(k_cache, "batch", "seq", None, None)
        v_cache = ctx.constrain(v_cache, "batch", "seq", None, None)
        new_cache = {"k": k_cache, "v": v_cache}
        k, v = k_cache.astype(cdt), v_cache.astype(cdt)
        Smax = k.shape[1]
        slot = jnp.arange(Smax, dtype=jnp.int32)[None, :]
        # slots beyond the write head are unwritten -> kpos=-1 (masked)
        written = slot <= (write_pos[:, None] + Sq - 1)
        kpos = jnp.where(written, slot, -1)
        causal = True

    Hp = cfg.padded_heads
    # is_global is usually a traced scalar (the layer scan carries the
    # local/global pattern as data); the kernel needs a STATIC window, so
    # the pallas path applies when the window question is static: either
    # is_global is a python bool, or the config has no window at all.
    # Under a ('data','model') serving mesh the jnp paged path partitions
    # through GSPMD (pool KV heads over 'model' — see ``paged_pool_spec``);
    # the kernel runs per shard on local head slices instead
    # (``kops.paged_attention`` routes through ``paged_attention_sharded``
    # under the engine's ``sharding.ctx.use_kernel_mesh``).
    static_global = isinstance(is_global, bool)
    use_paged_kernel = (
        block_tables is not None and cfg.use_pallas and Sq == 1
        and not cross and Hp == cfg.n_heads
        and cfg.n_heads % max(cfg.n_kv_heads, 1) == 0
        and cfg.meta_tokens == 0
        and (static_global or cfg.window is None)
        and jax.default_backend() == "tpu")
    use_pallas = (
        cfg.use_pallas and cache is None and not cross
        and Hp == cfg.n_heads and cfg.n_heads % max(cfg.n_kv_heads, 1) == 0
        and cfg.meta_tokens == 0
        and (static_global or cfg.window is None))
    if use_paged_kernel:
        # TPU hot path for paged decode: gather K/V page-by-page through
        # the block table inside the kernel (the jnp gather above is dead
        # code XLA eliminates).  Context length = write position + 1.
        from repro.kernels import ops as kops
        window = cfg.window if static_global and not is_global else None
        # tuned=True: num_splits (the flash-decoding grid axis) resolves
        # from the installed tuning cache at trace time, like the flash
        # path's blocks — the serving engine installs its autotuner
        # around _step, so long contexts pick their tuned split factor.
        # The scope names the kernel's device ops in a profiler trace
        # whatever the wrappers inside it are called.
        with jax.named_scope("paged_attention"):
            out_h = kops.paged_attention(
                q[:, 0], new_cache["k"], new_cache["v"], block_tables,
                write_pos + 1, scale=scale, window=window,
                softcap=cfg.attn_softcap, tuned=True, layer=layer)[:, None]
    elif use_pallas:
        # TPU hot path: the blocked flash kernel (kernels/flash_attention);
        # ragged sequence tails are padded+masked inside the kernel.
        # tuned=True resolves block_q/block_k/acc_dtype from the installed
        # autotuner's cache (repro.core.autotune); without one the kernel's
        # MXU-aligned defaults apply.
        from repro.kernels import ops as kops
        window = cfg.window if static_global and not is_global else None
        out_h = kops.flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_softcap, scale=scale, tuned=True)
    else:
        out_h = attend(q, k, v, positions, kpos, scale=scale, causal=causal,
                       window=None if cross else cfg.window,
                       n_sink=cfg.meta_tokens, cap=cfg.attn_softcap,
                       chunk=cfg.attn_chunk, is_global=is_global,
                       kv_map=cfg.kv_head_map() if Hp != cfg.n_heads else None)
    if Hp != cfg.n_heads:
        # zero the dead padded heads: outputs AND their weight grads vanish
        head_mask = (jax.lax.iota(jnp.int32, Hp) < cfg.n_heads)
        out_h = out_h * head_mask[None, None, :, None].astype(out_h.dtype)
    out_h = ctx.constrain(out_h.reshape(B, Sq, Hp * hd),
                          "batch", None, "model")
    if pre_output:
        return out_h, new_cache
    out = jnp.einsum("bsz,zd->bsd",
                     out_h, p["wo"].astype(cdt).reshape(Hp * hd, -1))
    return ctx.constrain(out, "batch", None, None), new_cache


def init_kv_cache(cfg, batch, max_len, n_layers, dtype=jnp.bfloat16):
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (n_layers, batch, max_len, kh, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_pool_spec(cfg, mesh=None):
    """The paged pool's partition spec ``[L, n_blocks, bs, KH, hd]``: KV
    heads over ``'model'`` (each model shard owns a head slice of EVERY
    block, so block ids — and the host-side allocator / eviction /
    compaction bookkeeping built on them — stay global), everything else
    replicated.  Falls back to full replication when the mesh's model
    axis cannot divide the KV heads evenly (``sanitize_specs`` rule)."""
    if mesh is not None and mesh.shape.get("model", 1) > 1 \
            and cfg.n_kv_heads % mesh.shape["model"] == 0:
        return P(None, None, None, "model", None)
    return P()


def init_paged_kv_cache(cfg, n_blocks, block_size, n_layers,
                        dtype=jnp.bfloat16, mesh=None):
    """The paged pool: ``n_blocks`` shared blocks of ``block_size`` token
    slots per layer — resident KV bytes scale with the pool, not with
    ``max_batch x max_len``.  With a ``mesh``, the pool is laid out
    sharded at birth (``paged_pool_spec``: KV heads over ``'model'``),
    so a sharded replica never materializes the replicated pool."""
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (n_layers, n_blocks, block_size, kh, hd)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if mesh is not None:
        from jax.sharding import NamedSharding
        sh = NamedSharding(mesh, paged_pool_spec(cfg, mesh))
        pool = jax.device_put(pool, {"k": sh, "v": sh})
    return pool


def kv_cache_specs(batch_axes=("data",), seq_axis="model"):
    """Decode caches shard batch over data and SEQUENCE over the model axis
    (flash-decode style) so tiny-kv-head archs (gemma3 kv=1) still scale."""
    spec = P(None, batch_axes, seq_axis, None, None)
    return {"k": spec, "v": spec}
