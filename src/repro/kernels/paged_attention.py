"""Paged-attention decode kernel (TPU Pallas).

Single-token decode attention over a paged KV cache: K/V live in a fixed
pool of ``[n_pages, block_size]`` token pages and each sequence names its
pages through a block table, so the kernel gathers exactly the pages a
context occupies instead of streaming a ``max_len`` stripe per sequence —
the block size *is* the memory-access granularity, which is what the
paper's hierarchy tables price.

Grid is ``(batch, heads)`` — or ``(batch, heads, num_splits)`` in the
split-KV "flash-decoding" form.  A query head's KV head is
``h // (H / KH)``, and the inner loop walks the sequence's valid pages
with the online-softmax (m, l, acc) recurrence.  Block tables and
context lengths are scalar-prefetched into SMEM
(``PrefetchScalarGridSpec``), so page ids and the loop trip count — the
sequence's own ``ceil(ctx / block_size)`` — are scalars: short contexts
cost few iterations regardless of the table width.  q and the outputs
carry a unit axis before ``D`` so every block's last two dimensions
equal the array's, which the TPU's (8, 128) tiling rule accepts.

Split-KV decoding (``num_splits > 1``): one ``(b, h)`` cell otherwise
serializes the whole context on one core while the rest of the chip
idles — the memory-latency-hiding bound the paper measures.  The split
form partitions a sequence's valid pages into ``num_splits`` contiguous
slices; each slice runs the same recurrence independently over pages
``[lo, hi)`` and emits its *partial* ``(m, l, acc)`` row, and a second
pass merges partials with the standard log-sum-exp rescale
(``_merge_partials``).  A split whose slice is empty (``lo >= hi`` —
``num_splits`` exceeds the sequence's valid pages, or ``ctx == 0``)
runs zero iterations and emits the identity partial
``(m=NEG_INF, l=0, acc=0)``, which the merge weights to exactly zero.

The pure-jnp oracle is ``repro.kernels.ref.paged_attention_ref`` (what
CPU CI asserts against); the model-side reference path used by the paged
serving engine lives in ``models.layers.attention`` (it also handles the
paged *write*).

Two lowerings share one wrapper signature:

* ``paged_attention`` — the in_specs declare the whole page pool as one
  block per grid cell.  Exact in interpret mode and fine for CI-sized
  pools, but it stages the *pool* into VMEM.
* ``paged_attention_hbm`` — the HBM-resident lowering: ``k_pages`` /
  ``v_pages`` stay in ``ANY``/HBM memory space and each loop iteration
  async-copies only the table-selected page into a double-buffered VMEM
  scratch (page ``j+1``'s DMA is issued before page ``j`` is consumed),
  so VMEM holds exactly two K pages + two V pages + the q/acc rows,
  independent of pool size.  A copy carries all ``KH`` heads of its
  page: the head axis is tiled, and the DMA cannot cut one head out of
  it, so each ``(b, h)`` cell reads ``KH`` times the bytes it attends
  (grouping the query heads of one KV head into one cell removes
  that).  The double-buffer pipeline is per-split:
  each split's slice walks its own consecutive ``j`` range, so the
  two-slot parity scheme works unchanged and VMEM still holds exactly
  two K + two V pages per grid cell regardless of ``num_splits``.

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


def _attend_page(q, k, v, raw, j, ctx, carry, *, window, softcap,
                 block_size):
    """One online-softmax step over page ``j`` — shared by all four
    kernel bodies so the split and unsplit lowerings compute the same
    math on the same page in the same order.  Every value is 2-D
    (``m``/``l`` are ``[1, 1]``, ``acc`` is ``[1, D]``): the TPU's
    vector layouts tile the last two dimensions."""
    m, l, acc = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, bs]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    # in-ctx positions whose table entry is -1 (unbacked page) must
    # mask, not attend the clipped page 0 — matches the ref oracle
    mask = (k_pos < ctx) & (raw >= 0)                     # causal by layout
    if window is not None:
        mask &= (ctx - 1 - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _carry_init(D):
    return (jnp.full((1, 1), NEG_INF, jnp.float32),
            jnp.zeros((1, 1), jnp.float32),
            jnp.zeros((1, D), jnp.float32))


def _split_bounds(ctx, block_size, num_splits):
    """[lo, hi) page range of this grid cell's split — contiguous slices
    of the sequence's valid pages; trailing splits may be empty."""
    n_valid = pl.cdiv(ctx, block_size)                    # traced trip count
    pages_per_split = pl.cdiv(n_valid, num_splits)
    lo = pl.program_id(2) * pages_per_split
    hi = jnp.minimum(lo + pages_per_split, n_valid)
    return lo, hi


def _merge_partials(m, l, acc, out_dtype):
    """Second flash-decoding pass: fold per-split partial softmax rows
    (``m/l [B,H,S]``, ``acc [B,H,S,D]``) with the log-sum-exp rescale.
    Identity partials (m=NEG_INF, l=0, acc=0) get weight exp(-huge)=0;
    all-identity rows (ctx == 0) divide 0 by the 1e-30 floor and come
    out all-zero, matching the oracle."""
    m_star = jnp.max(m, axis=-1, keepdims=True)           # [B,H,1]
    alpha = jnp.exp(m - m_star)                           # [B,H,S]
    l_star = jnp.sum(l * alpha, axis=-1)                  # [B,H]
    out = jnp.sum(acc * alpha[..., None], axis=2)         # [B,H,D]
    return (out / jnp.maximum(l_star, 1e-30)[..., None]).astype(out_dtype)


def _row(bt_ref, ctx_ref, n_blocks):
    """This grid cell's sequence: its context length and a reader of its
    block-table entries (both scalar-prefetched into SMEM)."""
    b = pl.program_id(0)
    return ctx_ref[b], lambda j: bt_ref[b * n_blocks + j]


def _head_rows(page, kh):
    """KV head ``kh``'s ``[bs, D]`` rows of one ``[bs, KH, D]`` page,
    kept with a one-hot sum: the head axis is the tiled second-minor
    axis, which neither a dynamic index nor a one-head DMA slice may cut."""
    page = page.astype(jnp.float32)
    sel = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1) == kh
    return jnp.sum(jnp.where(sel, page, 0.0), axis=1)


def _pa_staged_loop(q_ref, table, ctx, k_ref, v_ref, *, scale, window,
                    softcap, block_size, n_pages, kh, lo, hi):
    """The recurrence over pages ``[lo, hi)`` of the VMEM-staged pool."""
    q = q_ref[...].astype(jnp.float32) * scale            # [1, D]

    def body(j, carry):
        raw = table(j)
        pid = jnp.clip(raw, 0, n_pages - 1)
        return _attend_page(q, _head_rows(k_ref[pid], kh),
                            _head_rows(v_ref[pid], kh), raw, j, ctx,
                            carry, window=window, softcap=softcap,
                            block_size=block_size)

    return jax.lax.fori_loop(lo, hi, body, _carry_init(q.shape[-1]))


def _pa_hbm_loop(q_ref, table, ctx, k_hbm, v_hbm, *, scale, window,
                 softcap, block_size, n_pages, kh, lo, hi):
    """The double-buffered DMA pipeline over pages ``[lo, hi)``: issue
    page ``j+1``'s copies before waiting on page ``j`` so the gather
    overlaps the compute.  A copy moves the whole ``[bs, KH, D]`` page
    (the DMA cannot cut one head out of the tiled head axis) and
    ``_head_rows`` keeps this cell's head.  ``j`` runs consecutively
    within the range, so the two-slot parity scheme (``slot = j % 2``)
    holds for any split's ``lo`` — VMEM cost is two K + two V pages
    regardless of how many splits share the sequence.  Returns the
    final carry."""
    q = q_ref[...].astype(jnp.float32) * scale            # [1, D]
    D = q.shape[-1]

    def body(k_buf, v_buf, k_sem, v_sem):
        def dma(buf, hbm, sem, slot, j):
            pid = jnp.clip(table(j), 0, n_pages - 1)
            return pltpu.make_async_copy(hbm.at[pid], buf.at[slot],
                                         sem.at[slot])

        @pl.when(hi > lo)
        def _():
            slot0 = jax.lax.rem(lo, 2)
            dma(k_buf, k_hbm, k_sem, slot0, lo).start()
            dma(v_buf, v_hbm, v_sem, slot0, lo).start()

        def step(j, carry):
            slot = jax.lax.rem(j, 2)
            nxt = jax.lax.rem(j + 1, 2)

            @pl.when(j + 1 < hi)
            def _():
                dma(k_buf, k_hbm, k_sem, nxt, j + 1).start()
                dma(v_buf, v_hbm, v_sem, nxt, j + 1).start()

            dma(k_buf, k_hbm, k_sem, slot, j).wait()
            dma(v_buf, v_hbm, v_sem, slot, j).wait()
            k = _head_rows(k_buf[slot], kh)               # [bs, D]
            v = _head_rows(v_buf[slot], kh)
            return _attend_page(q, k, v, table(j), j, ctx, carry,
                                window=window, softcap=softcap,
                                block_size=block_size)

        return jax.lax.fori_loop(lo, hi, step, _carry_init(D))

    return pl.run_scoped(
        body,
        k_buf=pltpu.VMEM((2,) + k_hbm.shape[1:], k_hbm.dtype),
        v_buf=pltpu.VMEM((2,) + v_hbm.shape[1:], v_hbm.dtype),
        k_sem=pltpu.SemaphoreType.DMA((2,)),
        v_sem=pltpu.SemaphoreType.DMA((2,)))


def _pa_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, *out_refs, loop,
               n_blocks, group, num_splits, **kw):
    """One ``(b, h)`` — or ``(b, h, s)`` split — grid cell of either
    lowering.  ``loop`` walks the cell's page range (staged pool or HBM
    DMA pipeline); the unsplit form writes the normalized output row,
    the split form its partial ``(m, l, acc)`` row."""
    ctx, table = _row(bt_ref, ctx_ref, n_blocks)
    kh = pl.program_id(1) // group                        # GQA panel
    if num_splits == 1:
        lo, hi = jnp.int32(0), pl.cdiv(ctx, kw["block_size"])
    else:
        lo, hi = _split_bounds(ctx, kw["block_size"], num_splits)
    m, l, acc = loop(q_ref, table, ctx, k_ref, v_ref, kh=kh, lo=lo, hi=hi,
                     **kw)
    if num_splits == 1:
        (o_ref,) = out_refs
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    else:
        m_ref, l_ref, acc_ref = out_refs
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc


def _paged_call(q, k_pages, v_pages, block_tables, context_lens, *, hbm,
                scale, window, softcap, num_splits, interpret):
    """Build and run the ``pallas_call`` of either lowering.

    Block tables (flattened) and context lengths are scalar-prefetched
    into SMEM, so page ids and trip counts are read as scalars.  q and
    the outputs carry a unit axis before ``D`` (``[B, H, 1, D]``) so the
    last two block dimensions equal the array's, as the TPU's tiling
    requires; the head and split axes are squeezed grid axes."""
    B, H, D = q.shape
    P, bs, KH, _ = k_pages.shape
    NB = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    num_splits = max(int(num_splits), 1)
    grid = (B, H) if num_splits == 1 else (B, H, num_splits)
    row_map = lambda b, h, *_: (b, h, 0, 0)               # noqa: E731
    if hbm:
        pool_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    else:
        pool_spec = pl.BlockSpec((P, bs, KH, D), lambda *_: (0, 0, 0, 0))
    in_specs = [pl.BlockSpec((None, None, 1, D), row_map), pool_spec,
                pool_spec]
    if num_splits == 1:
        out_specs = pl.BlockSpec((None, None, 1, D), row_map)
        out_shape = jax.ShapeDtypeStruct((B, H, 1, D), q.dtype)
    else:
        part_map = lambda b, h, s, *_: (b, h, s, 0, 0)    # noqa: E731
        out_specs = [pl.BlockSpec((None, None, None, 1, 1), part_map),
                     pl.BlockSpec((None, None, None, 1, 1), part_map),
                     pl.BlockSpec((None, None, None, 1, D), part_map)]
        out_shape = [
            jax.ShapeDtypeStruct((B, H, num_splits, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, num_splits, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, num_splits, 1, D), jnp.float32)]
    kernel = functools.partial(
        _pa_kernel, loop=_pa_hbm_loop if hbm else _pa_staged_loop,
        n_blocks=NB, group=H // KH, num_splits=num_splits, scale=scale,
        window=window, softcap=softcap, block_size=bs, n_pages=P)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32).reshape(B * NB),
      jnp.asarray(context_lens, jnp.int32).reshape(B),
      q.reshape(B, H, 1, D), k_pages, v_pages)
    if num_splits == 1:
        return out.reshape(B, H, D)
    m, l, acc = out
    return _merge_partials(m.reshape(B, H, num_splits),
                           l.reshape(B, H, num_splits),
                           acc.reshape(B, H, num_splits, D), q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    scale=None, window=None, softcap=None, num_splits=1,
                    interpret=False):
    """q [B,H,D]; k/v_pages [P,bs,KH,D]; block_tables [B,NB] int32 (-1 =
    unbacked); context_lens [B] int32 -> [B,H,D].

    Attention of one new token per sequence over its paged context: the
    query position is ``context_lens - 1`` (causality holds by
    construction — only written positions are < ctx), with optional
    sliding ``window`` and logit ``softcap`` matching the flash kernel.
    Rows with ``context_lens == 0`` produce zeros (masked everywhere).

    ``num_splits > 1`` selects the split-KV flash-decoding form: grid
    ``(B, H, num_splits)``, per-split partial (m, l, acc) rows, and a
    log-sum-exp merge pass — same outputs up to summation order.
    """
    return _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                       hbm=False, scale=scale, window=window,
                       softcap=softcap, num_splits=num_splits,
                       interpret=interpret)


def paged_attention_hbm(q, k_pages, v_pages, block_tables, context_lens, *,
                        scale=None, window=None, softcap=None, num_splits=1,
                        interpret=False):
    """``paged_attention`` with the page pool kept in HBM (``ANY`` memory
    space) and per-page double-buffered async copies — the production
    lowering for pools far larger than VMEM.  Same contract and oracle
    (``ref.paged_attention_ref``) as the staged lowering, including the
    ``num_splits`` flash-decoding form."""
    return _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                       hbm=True, scale=scale, window=window,
                       softcap=softcap, num_splits=num_splits,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# the sharded (mesh) route
# ---------------------------------------------------------------------------


def paged_attention_sharded(q, k_pages, v_pages, block_tables, context_lens,
                            mesh, *, scale=None, window=None, softcap=None,
                            num_splits=1, hbm=False, interpret=False):
    """Per-shard head slices of the paged kernel over a ``('data',
    'model')`` mesh: query heads and KV heads split over ``'model'``,
    batch rows over ``'data'``, block tables and context lengths
    replicated per model shard.

    Head cells of the ``(B, H[, num_splits])`` grid are independent (a
    query head only ever reads its own KV-head group), so sharding is a
    pure index-space split: each model shard runs the SAME kernel on its
    local ``H/m`` query heads against its local ``KH/m`` KV-head slice
    of every page — the GQA group size ``H/KH`` is invariant under the
    split, and no cross-shard merge is needed (the split-KV log-sum-exp
    merge stays shard-local).  Falls back to the unsharded call when the
    mesh cannot divide heads/batch evenly (the ``sanitize_specs``
    replication rule) or has no parallelism at all."""
    from jax.sharding import PartitionSpec as P

    B, H, D = q.shape
    KH = k_pages.shape[2]
    scale = scale if scale is not None else D ** -0.5
    kern = paged_attention_hbm if hbm else paged_attention
    call = functools.partial(kern, scale=scale, window=window,
                             softcap=softcap, num_splits=num_splits,
                             interpret=interpret)
    d_sz, m_sz = mesh.shape["data"], mesh.shape["model"]
    head_ok = m_sz == 1 or (H % m_sz == 0 and KH % m_sz == 0)
    batch_ok = d_sz == 1 or B % d_sz == 0
    if (d_sz * m_sz == 1) or not head_ok:
        return call(q, k_pages, v_pages, block_tables, context_lens)
    bax = "data" if (d_sz > 1 and batch_ok) else None
    hax = "model" if m_sz > 1 else None
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(P(bax, hax, None),          # q: rows x head slice
                  P(None, None, hax, None),   # pools: KV-head slice
                  P(None, None, hax, None),
                  P(bax, None),               # tables: replicated per shard
                  P(bax,)),                   # context lengths
        out_specs=P(bax, hax, None),
        check_vma=False,
    )(q, k_pages, v_pages, block_tables, context_lens)
