"""Paged-attention decode kernel (TPU Pallas).

Single-token decode attention over a paged KV cache: K/V live in a fixed
pool of ``[n_pages, block_size]`` token pages and each sequence names its
pages through a block table, so the kernel gathers exactly the pages a
context occupies instead of streaming a ``max_len`` stripe per sequence —
the block size *is* the memory-access granularity, which is what the
paper's hierarchy tables price.

Grid is ``(batch,)`` — or ``(batch, num_splits)`` in the split-KV
"flash-decoding" form.  One cell serves all ``H`` query heads of its
sequence, so it reads each KV page the row names once, all ``KH`` heads
together.  The inner loop walks the row's pages a *span* at a time:
``max(1, 128 // block_size)`` consecutive table entries, so a step
covers 128 tokens and a row takes ``ceil(ctx / 128)`` serialized steps.
A span is viewed as ``[span * block_size * KH, D]`` — column ``c`` holds
token ``c // KH`` and KV head ``c % KH`` — and scored against every
query head at once (``[H, D] x [D, columns]``).  Columns of another KV
head than a query head's group ``h // (H / KH)`` are masked together
with the position, ``-1``-entry, window and softcap rules, so the
``p @ V`` that follows is each head's own weighted sum: ``KH`` times the
MXU work of a grouped product, on an MXU that decode otherwise leaves
idle, for two full-width matmuls and no transposes.  The online-softmax
(m, l, acc) recurrence runs row-wise, one row per query head.  Block
tables, context lengths and the layer are scalar-prefetched into SMEM
(``PrefetchScalarGridSpec``), so page ids and the loop trip count — the
sequence's own span count — are scalars: short contexts cost few
iterations regardless of the table width.

The pool may be one layer's ``[P, bs, KH, D]`` or the model's stacked
``[L, P, bs, KH, D]`` with a ``layer`` index: the kernel reads that
layer's pages where they lie, so a layer scan that carries the whole
pool hands it over without slicing it.  A one-layer pool is the same
call on ``pool[None]`` at layer 0.  q and the outputs enter as
``[B, H, D]`` blocks of ``(None, H, D)``, whose last two dimensions
equal the array's, as the TPU's (8, 128) tiling rule asks.

Split-KV decoding (``num_splits > 1``): one row's cell otherwise
serializes the whole context on one core.  The split form partitions a
sequence's spans into ``num_splits`` contiguous slices; each slice runs
the same recurrence independently over spans ``[lo, hi)`` and emits its
*partial* ``(m, l, acc)`` rows, and a second pass merges partials with
the standard log-sum-exp rescale (``_merge_partials``).  A split whose
slice is empty (``lo >= hi`` — ``num_splits`` exceeds the sequence's
spans, or ``ctx == 0``) runs zero iterations and emits the identity
partial ``(m=NEG_INF, l=0, acc=0)``, which the merge weights to exactly
zero.

The pure-jnp oracle is ``repro.kernels.ref.paged_attention_ref`` (what
CPU CI asserts against); the model-side reference path used by the paged
serving engine lives in ``models.layers.attention`` (it also handles the
paged *write*).

Two lowerings share one kernel body and one wrapper signature:

* ``paged_attention`` — the in_specs declare the layer's whole page
  pool as one block per grid cell, and the span copies run VMEM to
  VMEM.  Exact in interpret mode and fine for CI-sized pools, but it
  stages the layer's *pool* into VMEM.
* ``paged_attention_hbm`` — the HBM-resident lowering: ``k_pages`` /
  ``v_pages`` stay in ``ANY``/HBM memory space and each loop iteration
  async-copies the span's table-selected pages of the layer into a
  double-buffered VMEM scratch (span ``j+1``'s copies are issued
  before span ``j`` is consumed), so VMEM holds exactly two K spans +
  two V spans + the q/acc rows, independent of pool size.  A copy
  moves one whole ``[bs, KH, D]`` page; span pages past the row's valid
  pages, and ``-1`` entries, fetch the clipped page, whose positions
  are masked.
  The pipeline is per split: each split walks its own consecutive ``j``
  range, so the two-slot parity scheme works for any ``num_splits``.

``kernels.ops.paged_attention`` routes to the HBM lowering on real TPUs
(and on request in interpret mode, which CPU CI asserts against the
oracle); the staged lowering remains the small-pool/debug path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _attend_span(q, k_span, v_span, j, carry, *, table, ctx, window,
                 softcap, block_size):
    """One online-softmax step over span ``j`` for every query head.
    ``q`` is ``[H, D]`` f32 (scaled); ``k_span``/``v_span`` hold the
    span's ``[n, bs, KH, D]`` pages.  Upcast first, then collapse to
    ``[n * bs * KH, D]``: an f32 page tiles ``(8, 128)``, so the collapse
    moves no data.  ``m``/``l`` are ``[H, 1]`` and ``acc`` is ``[H, D]``:
    the TPU's vector layouts tile the last two dimensions."""
    m, l, acc = carry
    H, D = q.shape
    n, _, KH, _ = k_span.shape
    k = k_span[...].astype(jnp.float32).reshape(-1, D)    # [C, D]
    v = v_span[...].astype(jnp.float32).reshape(-1, D)
    C = k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [H, C]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    tok = col // KH                                       # token in span
    k_pos = j * (n * block_size) + tok
    mask = k_pos < ctx                                    # causal by layout
    if window is not None:
        mask &= (ctx - 1 - k_pos) < window

    # in-ctx positions whose table entry is -1 (unbacked page) must
    # mask, not attend the clipped page — matches the ref oracle
    page = tok // block_size
    raw = jax.lax.fori_loop(
        0, n, lambda i, raw: jnp.where(page == i, table(j * n + i), raw),
        jnp.zeros_like(page))                             # [1, C] entries
    mask &= raw >= 0
    # a query head attends only its own KV head's columns
    group = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // (H // KH)
    mask = mask & (col - tok * KH == group)               # [H, C]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _carry_init(H, D):
    return (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, D), jnp.float32))


def _split_bounds(n_spans, num_splits):
    """[lo, hi) span range of this grid cell's split — contiguous slices
    of the sequence's spans; trailing splits may be empty."""
    per_split = pl.cdiv(n_spans, num_splits)
    lo = pl.program_id(1) * per_split
    hi = jnp.minimum(lo + per_split, n_spans)
    return lo, hi


def _merge_partials(m, l, acc, out_dtype):
    """Second flash-decoding pass: fold per-split partial softmax rows
    (``m/l [B,S,H,1]``, ``acc [B,S,H,D]``) over ``S`` with the
    log-sum-exp rescale.  Identity partials (m=NEG_INF, l=0, acc=0) get
    weight exp(-huge)=0; all-identity rows (ctx == 0) divide 0 by the
    1e-30 floor and come out all-zero, matching the oracle."""
    m_star = jnp.max(m, axis=1, keepdims=True)            # [B,1,H,1]
    alpha = jnp.exp(m - m_star)                           # [B,S,H,1]
    l_star = jnp.sum(l * alpha, axis=1)                   # [B,H,1]
    out = jnp.sum(acc * alpha, axis=1)                    # [B,H,D]
    return (out / jnp.maximum(l_star, 1e-30)).astype(out_dtype)


def _row(bt_ref, ctx_ref, n_blocks):
    """This grid cell's sequence: its context length and a reader of its
    block-table entries (both scalar-prefetched into SMEM).  Entries past
    the table's width read its last one: a span may run past the table,
    and those positions lie past ``ctx``."""
    b = pl.program_id(0)
    return ctx_ref[b], lambda j: bt_ref[b * n_blocks
                                        + jnp.minimum(j, n_blocks - 1)]


def _pa_span_loop(table, k_pool, v_pool, layer, *, attend, init, span,
                  n_pages, lo, hi):
    """The double-buffered copy pipeline over spans ``[lo, hi)``: issue
    span ``j+1``'s page copies before waiting on span ``j`` so the
    gather overlaps the compute.  A span is ``span`` whole-page copies
    each of K and V, all signalling their slot's semaphore; the pool
    ``[L, P, bs, KH, D]`` is HBM-resident or VMEM-staged, the copies are
    the same: page ``pid`` of ``layer``.  ``j`` runs
    consecutively within the range, so the two-slot parity scheme
    (``slot = j % 2``) holds for any split's ``lo`` — VMEM cost is two K
    + two V spans regardless of how many splits share the sequence.
    Returns the final carry."""
    def body(k_buf, v_buf, k_sem, v_sem):
        def copies(slot, j, i):
            pid = jnp.clip(table(j * span + i), 0, n_pages - 1)
            return (pltpu.make_async_copy(k_pool.at[layer, pid],
                                          k_buf.at[slot, i], k_sem.at[slot]),
                    pltpu.make_async_copy(v_pool.at[layer, pid],
                                          v_buf.at[slot, i], v_sem.at[slot]))

        def each_page(slot, j, op):
            def one(i, _):
                for c in copies(slot, j, i):
                    op(c)
            jax.lax.fori_loop(0, span, one, None)

        @pl.when(hi > lo)
        def _():
            each_page(jax.lax.rem(lo, 2), lo, lambda c: c.start())

        def step(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < hi)
            def _():
                each_page(jax.lax.rem(j + 1, 2), j + 1, lambda c: c.start())

            each_page(slot, j, lambda c: c.wait())
            return attend(k_buf.at[slot], v_buf.at[slot], j, carry)

        return jax.lax.fori_loop(lo, hi, step, init)

    return pl.run_scoped(
        body,
        k_buf=pltpu.VMEM((2, span) + k_pool.shape[2:], k_pool.dtype),
        v_buf=pltpu.VMEM((2, span) + v_pool.shape[2:], v_pool.dtype),
        k_sem=pltpu.SemaphoreType.DMA((2,)),
        v_sem=pltpu.SemaphoreType.DMA((2,)))


def _pa_kernel(bt_ref, ctx_ref, layer_ref, q_ref, k_ref, v_ref, *out_refs,
               n_blocks, num_splits, scale, window, softcap, block_size,
               n_pages, hbm):
    """One row's grid cell — or one ``(b, s)`` split — of either
    lowering, for all ``H`` query heads: walk the cell's span range,
    then write the normalized ``[H, D]`` output (unsplit) or the
    partial ``(m, l, acc)`` rows (split).  The staged lowering's block
    already holds only the prefetched layer, at index 0."""
    ctx, table = _row(bt_ref, ctx_ref, n_blocks)
    span = max(1, 128 // block_size)      # table entries per loop step
    n_spans = pl.cdiv(ctx, span * block_size)             # traced trip count
    if num_splits == 1:
        lo, hi = jnp.int32(0), n_spans
    else:
        lo, hi = _split_bounds(n_spans, num_splits)
    q = q_ref[...].astype(jnp.float32) * scale            # [H, D]
    attend = functools.partial(_attend_span, q, table=table, ctx=ctx,
                               window=window, softcap=softcap,
                               block_size=block_size)
    layer = layer_ref[0] if hbm else 0
    m, l, acc = _pa_span_loop(table, k_ref, v_ref, layer, attend=attend,
                              init=_carry_init(*q.shape), span=span,
                              n_pages=n_pages, lo=lo, hi=hi)
    if num_splits == 1:
        (o_ref,) = out_refs
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    else:
        m_ref, l_ref, acc_ref = out_refs
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc


def _paged_call(q, k_pages, v_pages, block_tables, context_lens, layer, *,
                hbm, scale, window, softcap, num_splits, interpret):
    """Build and run the ``pallas_call`` of either lowering.

    Block tables (flattened), context lengths and the layer are
    scalar-prefetched into SMEM, so page ids and trip counts are read as
    scalars.  Every block holds a row's whole ``[H, D]`` (or ``[H, 1]``)
    — its last two dimensions equal the array's, as the TPU's tiling
    requires; the batch and split axes are squeezed grid axes.  A
    one-layer pool ``[P, bs, KH, D]`` runs as ``[1, P, bs, KH, D]`` at
    layer 0 (``layer`` is then ignored)."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, H, D = q.shape
    _, P, bs, KH, _ = k_pages.shape
    NB = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    num_splits = max(int(num_splits), 1)
    grid = (B,) if num_splits == 1 else (B, num_splits)
    if hbm:
        pool_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    else:
        # index maps get the grid indices, then the prefetched refs
        pool_spec = pl.BlockSpec((1, P, bs, KH, D),
                                 lambda *a: (a[-1][0], 0, 0, 0, 0))
    in_specs = [pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0)),
                pool_spec, pool_spec]
    if num_splits == 1:
        out_specs = pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0))
        out_shape = jax.ShapeDtypeStruct((B, H, D), q.dtype)
    else:
        part_map = lambda b, s, *_: (b, s, 0, 0)          # noqa: E731
        out_specs = [pl.BlockSpec((None, None, H, 1), part_map),
                     pl.BlockSpec((None, None, H, 1), part_map),
                     pl.BlockSpec((None, None, H, D), part_map)]
        out_shape = [
            jax.ShapeDtypeStruct((B, num_splits, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, H, D), jnp.float32)]
    kernel = functools.partial(
        _pa_kernel, n_blocks=NB, num_splits=num_splits, scale=scale,
        window=window, softcap=softcap, block_size=bs, n_pages=P, hbm=hbm)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32).reshape(B * NB),
      jnp.asarray(context_lens, jnp.int32).reshape(B),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q, k_pages, v_pages)
    if num_splits == 1:
        return out
    return _merge_partials(*out, q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    layer=0, *, scale=None, window=None, softcap=None,
                    num_splits=1, interpret=False):
    """q [B,H,D]; k/v_pages [P,bs,KH,D], or the stacked [L,P,bs,KH,D]
    read at ``layer`` (an int32 scalar, may be traced); block_tables
    [B,NB] int32 (-1 = unbacked); context_lens [B] int32 -> [B,H,D].

    Attention of one new token per sequence over its paged context: the
    query position is ``context_lens - 1`` (causality holds by
    construction — only written positions are < ctx), with optional
    sliding ``window`` and logit ``softcap`` matching the flash kernel.
    Rows with ``context_lens == 0`` produce zeros (masked everywhere).

    ``num_splits > 1`` selects the split-KV flash-decoding form: grid
    ``(B, num_splits)``, per-split partial (m, l, acc) rows, and a
    log-sum-exp merge pass — same outputs up to summation order.
    """
    return _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                       layer, hbm=False, scale=scale, window=window,
                       softcap=softcap, num_splits=num_splits,
                       interpret=interpret)


def paged_attention_hbm(q, k_pages, v_pages, block_tables, context_lens,
                        layer=0, *, scale=None, window=None, softcap=None,
                        num_splits=1, interpret=False):
    """``paged_attention`` with the page pool kept in HBM (``ANY`` memory
    space) and per-span double-buffered async copies — the production
    lowering for pools far larger than VMEM.  Same contract and oracle
    (``ref.paged_attention_ref``) as the staged lowering, including the
    ``num_splits`` flash-decoding form."""
    return _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                       layer, hbm=True, scale=scale, window=window,
                       softcap=softcap, num_splits=num_splits,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# the sharded (mesh) route
# ---------------------------------------------------------------------------


def paged_attention_sharded(q, k_pages, v_pages, block_tables, context_lens,
                            mesh, layer=0, *, scale=None, window=None,
                            softcap=None, num_splits=1, hbm=False,
                            interpret=False):
    """Per-shard head slices of the paged kernel over a ``('data',
    'model')`` mesh: query heads and KV heads split over ``'model'``,
    batch rows over ``'data'``, block tables and context lengths
    replicated per model shard.

    Query heads are independent (a query head only ever reads its own
    KV-head group), so sharding is a pure index-space split: each model shard runs the SAME kernel on its
    local ``H/m`` query heads against its local ``KH/m`` KV-head slice
    of every page — the GQA group size ``H/KH`` is invariant under the
    split, and no cross-shard merge is needed (the split-KV log-sum-exp
    merge stays shard-local).  Falls back to the unsharded call when the
    mesh cannot divide heads/batch evenly (the ``sanitize_specs``
    replication rule) or has no parallelism at all."""
    from jax.sharding import PartitionSpec as P

    B, H, D = q.shape
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    KH = k_pages.shape[3]
    layer = jnp.asarray(layer, jnp.int32)
    scale = scale if scale is not None else D ** -0.5
    kern = paged_attention_hbm if hbm else paged_attention
    call = functools.partial(kern, scale=scale, window=window,
                             softcap=softcap, num_splits=num_splits,
                             interpret=interpret)
    d_sz, m_sz = mesh.shape["data"], mesh.shape["model"]
    head_ok = m_sz == 1 or (H % m_sz == 0 and KH % m_sz == 0)
    batch_ok = d_sz == 1 or B % d_sz == 0
    if (d_sz * m_sz == 1) or not head_ok:
        return call(q, k_pages, v_pages, block_tables, context_lens, layer)
    bax = "data" if (d_sz > 1 and batch_ok) else None
    hax = "model" if m_sz > 1 else None
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(P(bax, hax, None),            # q: rows x head slice
                  P(None, None, None, hax, None),   # pools: KV-head slice
                  P(None, None, None, hax, None),
                  P(bax, None),                 # tables: replicated per shard
                  P(bax,),                      # context lengths
                  P()),                         # the layer
        out_specs=P(bax, hax, None),
        check_vma=False,
    )(q, k_pages, v_pages, block_tables, context_lens, layer)
