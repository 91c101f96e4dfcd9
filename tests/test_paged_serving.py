"""Paged vs slot serving on the real (reduced) model, plus property tests
over the block allocator and random request traces.

The acceptance trace: 32 mixed-length requests through both engines with
the paged pool sized strictly below the slot engine's
``max_batch x max_len`` rectangle — identical greedy token ids, strictly
fewer resident KV bytes, leak-free teardown.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade: property tests skip, the rest run
    from _hypothesis_stub import given, settings, st

from repro.configs import ARCHS, reduced
from repro.models import transformer
from repro.models.layers import attention as attn_mod, basic
from repro.models.zoo import build_model
from repro.serve import PagedServingEngine, ServingEngine
from repro.serve.paging import BlockAllocator, remap_table


@functools.lru_cache(maxsize=None)
def _tiny():
    """Module-cached tiny model (lru_cache, not a fixture, so hypothesis
    can draw examples without fixture-scope health checks)."""
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _run_slot(model, params, prompts, max_new, max_batch=4, max_len=48):
    eng = ServingEngine(model, params, max_batch=max_batch, max_len=max_len)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_done()
    return eng, rids


def _run_paged(model, params, prompts, max_new, max_batch=4, max_len=48,
               **kw):
    eng = PagedServingEngine(model, params, max_batch=max_batch,
                             max_len=max_len, **kw)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_done(max_steps=20_000)
    return eng, rids


# ---------------------------------------------------------------------------
# the acceptance trace
# ---------------------------------------------------------------------------


def test_paged_engine_32_request_trace_identical_in_less_memory():
    """The ISSUE's acceptance bar: a 32-request mixed-length trace, KV
    memory strictly under the slot engine's, identical greedy tokens,
    simulation-verified leak-free teardown."""
    cfg, model, params = _tiny()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(1, 31))).astype(np.int32)
               for _ in range(32)]

    slot, rids_s = _run_slot(model, params, prompts, max_new=4)
    # 10 blocks of 8 tokens vs the slot rectangle's 4 x 48 = 24 blocks
    paged, rids_p = _run_paged(model, params, prompts, max_new=4,
                               block_size=8, n_blocks=10, chunk_size=8)

    assert paged.stats.completed == 32
    assert slot.stats.completed == 32
    assert paged.kv_cache_bytes() < slot.kv_cache_bytes()
    for rs, rp in zip(rids_s, rids_p):
        assert slot.done[rs].tokens == paged.done[rp].tokens, (rs, rp)
    paged.allocator.check()
    assert paged.allocator.n_free == paged.n_blocks   # block-leak free


def test_preemption_under_minimal_pool_still_identical():
    """The smallest legal pool (one max_len sequence) forces eviction
    churn; replayed requests must still produce the slot engine's
    tokens."""
    cfg, model, params = _tiny()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(1, 28))).astype(np.int32)
               for _ in range(8)]
    slot, rids_s = _run_slot(model, params, prompts, max_new=5)
    paged, rids_p = _run_paged(model, params, prompts, max_new=5,
                               block_size=8, n_blocks=6, chunk_size=8)
    assert paged.stats.completed == 8
    assert paged.stats.preemptions > 0
    for rs, rp in zip(rids_s, rids_p):
        assert slot.done[rs].tokens == paged.done[rp].tokens
    assert paged.allocator.n_free == paged.n_blocks


def test_compaction_off_still_correct():
    cfg, model, params = _tiny()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(s)).astype(np.int32)
               for s in rng.integers(1, 20, size=6)]
    slot, rids_s = _run_slot(model, params, prompts, max_new=4)
    paged, rids_p = _run_paged(model, params, prompts, max_new=4,
                               block_size=8, n_blocks=12, chunk_size=8,
                               compact_on_retire=False)
    assert paged.stats.compactions == 0
    for rs, rp in zip(rids_s, rids_p):
        assert slot.done[rs].tokens == paged.done[rp].tokens


def test_paged_engine_rejects_unpageable_archs():
    cfg = reduced(ARCHS["rwkv6-1.6b"])
    model = build_model(cfg)
    with pytest.raises(NotImplementedError):
        model.init_paged_cache(4, 8)


# ---------------------------------------------------------------------------
# allocator properties
# ---------------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(st.integers(1, 24),
       st.lists(st.tuples(st.booleans(), st.integers(0, 23)),
                max_size=120))
def test_allocator_never_double_allocates_and_frees_everything(n_blocks,
                                                               script):
    """Random alloc/free interleavings: every handed-out id is unique
    among live blocks, the pool partition invariant holds throughout, and
    freeing all live blocks restores the full pool."""
    alloc = BlockAllocator(n_blocks, block_size=4)
    live = []
    for do_alloc, pick in script:
        if do_alloc:
            b = alloc.alloc()
            if b is None:
                assert len(live) == n_blocks     # only fails when full
            else:
                assert b not in live             # never double-allocated
                live.append(b)
        elif live:
            b = live.pop(pick % len(live))
            alloc.free([b])
        alloc.check()
    alloc.free(live)
    alloc.check()
    assert alloc.n_free == n_blocks              # retire frees every block


@settings(max_examples=100, deadline=None)
@given(st.integers(2, 20), st.data())
def test_compaction_plan_densifies_and_remap_is_consistent(n_blocks, data):
    alloc = BlockAllocator(n_blocks, block_size=4)
    blocks = [alloc.alloc() for _ in range(n_blocks)]
    keep = data.draw(st.sets(st.sampled_from(blocks),
                             max_size=n_blocks - 1))
    alloc.free([b for b in blocks if b not in keep])
    plan = alloc.compaction_plan()
    table = sorted(keep) + [-1]
    if plan is None:
        assert sorted(keep) == list(range(len(keep)))    # already dense
        return
    src, dst = plan
    new_table = remap_table(table, src, dst)
    alloc.commit_compaction()
    alloc.check()
    # dense: the kept blocks now occupy exactly [0, len(keep))
    assert sorted(b for b in new_table if b >= 0) == list(range(len(keep)))
    assert new_table[-1] == -1                   # unbacked slots untouched
    assert alloc.watermark() == len(keep)


# ---------------------------------------------------------------------------
# trace property: paged == slot for random traces
# ---------------------------------------------------------------------------


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000),
       st.integers(2, 6),
       st.sampled_from([4, 8]),
       st.sampled_from([4, 8]))
def test_paged_matches_slot_on_random_traces(seed, n_req, block_size,
                                             chunk_size):
    """Greedy decode is deterministic, so for ANY trace the paged engine
    must reproduce the slot engine's token ids exactly — chunk/page size
    are implementation detail, not semantics."""
    cfg, model, params = _tiny()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(1, 25))).astype(np.int32)
               for _ in range(n_req)]
    slot, rids_s = _run_slot(model, params, prompts, max_new=4,
                             max_batch=3)
    paged, rids_p = _run_paged(model, params, prompts, max_new=4,
                               max_batch=3, block_size=block_size,
                               n_blocks=-(-48 // block_size) + 3,
                               chunk_size=chunk_size)
    for rs, rp in zip(rids_s, rids_p):
        assert slot.done[rs].tokens == paged.done[rp].tokens
    paged.allocator.check()
    assert paged.allocator.n_free == paged.n_blocks


def test_decode_chunk_equals_prefill_logits():
    """The chunked-prefill primitive itself: feeding a prompt through the
    decode path in chunks (with overlap and left-padding) must yield the
    prefill path's next-token distribution argmax."""
    cfg, model, params = _tiny()
    rng = np.random.default_rng(3)
    for S, C in [(1, 4), (3, 4), (4, 4), (9, 4), (13, 8)]:
        prompt = rng.integers(0, cfg.vocab_size, size=S).astype(np.int32)
        logits_p, _ = model.prefill(params, {"tokens": prompt[None]},
                                    max_len=32)
        want = int(jnp.argmax(logits_p[0]))

        cache = model.init_paged_cache(8, 4)
        bt = jnp.arange(8, dtype=jnp.int32)[None]
        filled, logits = 0, None
        while filled < S:
            end = min(filled + C, S)
            start = end - C
            toks = np.zeros(C, np.int32)
            lo = max(start, 0)
            toks[C - (end - lo):] = prompt[lo:end]
            logits, cache = model.decode(
                params, cache, jnp.asarray(toks[None]),
                jnp.asarray([start], jnp.int32), bt)
            filled = end
        assert int(jnp.argmax(logits[0])) == want, (S, C)


# ---------------------------------------------------------------------------
# the layer scan carries the pool and writes each layer's pages in place
# ---------------------------------------------------------------------------


def _one_layer_update_gather(cache, k_new, v_new, block_tables, write_pos,
                             layer=None):
    """The per-layer pool update the layer scan made before it carried
    the whole pool: one layer's ``[n_blocks, bs, KH, hd]`` pages (so no
    ``layer``), a flat scatter of the new tokens, a flat gather of the
    logical view."""
    ck, cv = cache["k"], cache["v"]
    nb, bs = ck.shape[0], ck.shape[1]
    B, Sq, KH, hd = k_new.shape
    NB = block_tables.shape[1]
    pos = write_pos[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(pos // bs, 0, NB - 1), axis=1)
    phys = jnp.where((pos >= 0) & (blk >= 0), blk * bs + pos % bs, nb * bs)

    def write(c, n):
        flat = c.reshape(nb * bs, KH, hd)
        flat = flat.at[phys.reshape(-1)].set(
            n.reshape(B * Sq, KH, hd).astype(c.dtype), mode="drop")
        return flat.reshape(c.shape)

    k_cache, v_cache = write(ck, k_new), write(cv, v_new)
    lslot = jnp.arange(NB * bs, dtype=jnp.int32)
    page = block_tables[:, lslot // bs]
    idx = jnp.where(page >= 0, page * bs + (lslot % bs)[None], 0)
    written = (page >= 0) & (lslot[None] <= write_pos[:, None] + Sq - 1)
    k = k_cache.reshape(nb * bs, KH, hd)[idx]
    v = v_cache.reshape(nb * bs, KH, hd)[idx]
    kpos = jnp.where(written, lslot[None], -1)
    return {"k": k_cache, "v": v_cache}, k, v, kpos


def _per_layer_scan(params, cfg, pool, tokens, write_pos, block_tables):
    """The paged decode as the layer scan ran it before it carried the
    pool: each layer's ``[n_blocks, bs, KH, hd]`` pages enter the scan
    as ``xs`` and leave as ``ys``, updated by the per-layer form."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = basic.embed_tokens(params["embed"], tokens, cdt,
                           scale_by_dim=cfg.scale_embeds)
    positions = (write_pos[:, None]
                 + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None])

    def body(x, xs):
        lp, g, cl = xs
        x, ncl, _ = transformer._layer(
            x, lp, cfg=cfg, positions=positions, is_global=g,
            cache_layer=cl, write_pos=write_pos, mode="decode",
            block_tables=block_tables)
        return x, ncl

    x, pool = jax.lax.scan(body, x, (params["layers"], jnp.asarray(
        cfg.global_layer_mask(), bool), pool))
    x = basic.rmsnorm(params["ln_f"], x, cfg.norm_eps)[:, -1:]
    logits = basic.unembed(params["embed"], x, cdt, cfg.logit_softcap,
                           vocab=cfg.vocab_size)
    return logits[:, -1], pool


def _scan_case(arch, call):
    """A 3-layer model, a pool of random pages, and one launch: four
    decode rows (one inactive at -1) or one 256-token chunk that starts
    left-padded; tables hold -1 entries past each row's pages."""
    if arch == "dense":
        cfg = reduced(ARCHS["internlm2-20b"], n_layers=3, vocab_size=128)
    else:
        cfg = reduced(ARCHS["olmoe-1b-7b"], n_layers=3, vocab_size=128)
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, n_experts=8, top_k=2, expert_shards=2, expert_shard=1))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    n_blocks, bs, NB = 40, 16, 20
    pool = model.init_paged_cache(n_blocks, bs)
    pool = {n: jax.random.normal(jax.random.PRNGKey(i), c.shape
                                 ).astype(c.dtype)
            for i, (n, c) in enumerate(sorted(pool.items()))}
    rng = np.random.default_rng(2)
    perm = rng.permutation(n_blocks).astype(np.int32)
    if call == "decode":
        write_pos = np.asarray([37, -1, 0, 150], np.int32)
        tokens = rng.integers(0, cfg.vocab_size, (4, 1)).astype(np.int32)
        tables = np.full((4, NB), -1, np.int32)
        for b, n in enumerate((3, 2, 1, 10)):
            tables[b, :n] = perm[:n] if b == 1 else perm[4 * b:4 * b + n]
    else:
        write_pos = np.asarray([-40], np.int32)
        tokens = rng.integers(0, cfg.vocab_size, (1, 256)).astype(np.int32)
        tables = np.full((1, NB), -1, np.int32)
        tables[0, :14] = perm[:14]
    return (cfg, model, params, pool, jnp.asarray(tokens),
            jnp.asarray(write_pos), jnp.asarray(tables))


@pytest.mark.parametrize("call", ["decode", "chunk"])
@pytest.mark.parametrize("arch", ["dense", "moe"])
def test_layer_scan_pool_update_matches_per_layer_slices(arch, call,
                                                         monkeypatch):
    """The scanned stack carries the whole pool and writes each layer's
    pages at ``[layer, block, slot]``; the per-layer scan it replaced,
    each layer's pages sliced out, updated and stacked back, gives the
    same tokens, the same logits and the same pool, bit for bit: dropped
    writes (negative positions, ``-1`` entries) included."""
    cfg, model, params, pool, tokens, write_pos, tables = _scan_case(
        arch, call)
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
    logits, new_pool = jax.jit(model.decode)(params, copy(pool), tokens,
                                             write_pos, tables)
    nxt = jax.jit(model.decode_step)(params, copy(pool), tokens, write_pos,
                                     tables)[0]
    monkeypatch.setattr(attn_mod, "_paged_update_gather",
                        _one_layer_update_gather)
    want_logits, want_pool = jax.jit(
        functools.partial(_per_layer_scan, cfg=cfg))(
        params, pool=pool, tokens=tokens, write_pos=write_pos,
        block_tables=tables)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    np.testing.assert_array_equal(np.asarray(nxt),
                                  np.asarray(jnp.argmax(want_logits, -1)))
    for n in ("k", "v"):
        for layer in range(cfg.n_layers):
            np.testing.assert_array_equal(np.asarray(new_pool[n][layer]),
                                          np.asarray(want_pool[n][layer]))
    # the launch wrote pages: two untouched pools would compare equal too
    assert not np.array_equal(np.asarray(new_pool["k"]), np.asarray(pool["k"]))
