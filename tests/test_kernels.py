"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret mode on CPU, per the task spec)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade: property tests skip, the rest run
    from _hypothesis_stub import given, settings, st

from repro.core.microbench.memory import _random_cycle
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 5e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 4, 2, 32), (2, 256, 4, 4, 64)])
@pytest.mark.parametrize("kw", [dict(causal=True),
                                dict(causal=True, window=64),
                                dict(causal=False),
                                dict(causal=True, softcap=30.0)])
def test_flash_attention_sweep(dtype, shape, kw):
    B, S, H, KH, D = shape
    q = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, KH, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, KH, D)), dtype)
    o = ops.flash_attention(q, k, v, block_q=64, block_k=64, **kw)
    r = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=4 * _tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("di,n,block", [(256, 8, 128), (512, 16, 256)])
def test_ssm_scan_sweep(dtype, di, n, block):
    Bt, S = 2, 32
    x = jnp.asarray(RNG.normal(size=(Bt, S, di)) * 0.2, dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(Bt, S, di)), dtype)
    Bm = jnp.asarray(RNG.normal(size=(Bt, S, n)) * 0.2, dtype)
    Cm = jnp.asarray(RNG.normal(size=(Bt, S, n)) * 0.2, dtype)
    A = -jnp.abs(jnp.asarray(RNG.normal(size=(di, n)), jnp.float32))
    o = ops.ssm_scan(x, dt, Bm, Cm, A, block_d=block)
    r = ref.ssm_scan_ref(x, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=10 * _tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("h,n", [(2, 32), (4, 64)])
def test_wkv6_sweep(dtype, h, n):
    B, S = 2, 24
    r_ = jnp.asarray(RNG.normal(size=(B, S, h, n)) * 0.3, dtype)
    k_ = jnp.asarray(RNG.normal(size=(B, S, h, n)) * 0.3, dtype)
    v_ = jnp.asarray(RNG.normal(size=(B, S, h, n)) * 0.3, dtype)
    w_ = jnp.asarray(RNG.uniform(0.7, 0.999, size=(B, S, h, n)), dtype)
    u_ = jnp.asarray(RNG.normal(size=(h, n)) * 0.3, dtype)
    o = ops.wkv6(r_, k_, v_, w_, u_)
    rr = ref.wkv6_ref(r_, k_, v_, w_, u_)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(rr, np.float32),
                               atol=10 * _tol(dtype))


@pytest.mark.parametrize("sq,skv,bq,bk", [
    (12, 13, 8, 8),        # kv tail: 13 % 8 != 0 (the silently-dropped case)
    (100, 100, 64, 64),    # both tails ragged
    (5, 9, 128, 128),      # blocks larger than the problem
    (37, 53, 16, 32),      # coprime everything
])
def test_flash_attention_ragged_tails(sq, skv, bq, bk):
    """seq % block != 0 must pad+mask, not drop the tail (regression: the
    old kernel computed n_blocks = seq_kv // block_k and lost the rest)."""
    q = jnp.asarray(RNG.normal(size=(2, sq, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, skv, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, skv, 2, 16)), jnp.float32)
    for kw in (dict(causal=False), dict(causal=True),
               dict(causal=True, window=7)):
        o = ops.flash_attention(q, k, v, block_q=bq, block_k=bk, **kw)
        r = ref.flash_attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(skv=st.integers(1, 70), bk=st.sampled_from([8, 16, 32, 64, 128]),
       causal=st.booleans())
def test_flash_attention_kv_boundary_property(skv, bk, causal):
    """Property: any (seq_kv, block_k) pair matches the reference — the
    padded tail is masked, never attended, never dropped."""
    rng = np.random.default_rng(skv * 1000 + bk)
    sq = max(skv - 2, 1)
    q = jnp.asarray(rng.normal(size=(1, sq, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, skv, 1, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, skv, 1, 8)), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=bk)
    r = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-4)


@pytest.mark.parametrize("op", ["add", "mul", "fma", "max", "div", "rsqrt",
                                "exp", "tanh", "select"])
@pytest.mark.parametrize("dependent", [True, False])
def test_alu_chain_sweep(op, dependent):
    x = jnp.asarray(RNG.normal(size=(8, 128)) + 2.0, jnp.float32)
    o = ops.alu_chain(x, 1.0009765625, op=op, length=12, dependent=dependent)
    r = ref.alu_chain_ref(x, jnp.float32(1.0009765625), op=op, length=12,
                          dependent=dependent)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-4,
                               atol=1e-4)


@pytest.mark.parametrize("n", [64, 512, 4096])
def test_pointer_chase_sweep(n):
    nxt = jnp.asarray(_random_cycle(n, seed=n))
    o = ops.pointer_chase(nxt, 0, hops=min(n, 257))
    r = ref.pointer_chase_ref(nxt, jnp.int32(0), min(n, 257))
    assert int(o) == int(r)


def test_pointer_chase_visits_whole_cycle():
    n = 128
    nxt = jnp.asarray(_random_cycle(n))
    assert int(ops.pointer_chase(nxt, 0, hops=n)) == 0  # full cycle


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,chain", [(128, 128, 128, 1),
                                         (128, 128, 128, 4),
                                         (256, 256, 128, 1)])
def test_mxu_probe_sweep(dtype, m, k, n, chain):
    a = jnp.asarray(RNG.normal(size=(m, k)) * 0.1, dtype)
    b = jnp.asarray(RNG.normal(size=(k, n)) * 0.1, dtype)
    o = ops.mxu_probe(a, b, chain=chain)
    r = ref.mxu_probe_ref(a, b, chain=chain)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=5 * _tol(dtype), rtol=2e-2)


# ---------------------------------------------------------------------------
# paged attention (decode through a block table)
# ---------------------------------------------------------------------------


def _paged_case(B, H, KH, D, bs, ctxs, n_pages, seed=0):
    """Random pages + per-row dense shuffled block tables for given
    context lengths."""
    rng = np.random.default_rng(seed)
    NB = max(-(-c // bs) for c in ctxs)
    q = jnp.asarray(rng.normal(size=(B, H, D)) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, bs, KH, D)) * 0.3, jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, bs, KH, D)) * 0.3, jnp.float32)
    perm = rng.permutation(n_pages)
    bt = np.full((B, NB), -1, np.int32)
    used = 0
    for b, c in enumerate(ctxs):
        nb = -(-c // bs)
        bt[b, :nb] = perm[used:used + nb]
        used += nb
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(ctxs, jnp.int32)


@pytest.mark.parametrize("hbm", [False, True])
@pytest.mark.parametrize("kw", [dict(), dict(window=5), dict(softcap=8.0),
                                dict(window=3, softcap=4.0)])
@pytest.mark.parametrize("bs,ctxs", [(4, (1, 7, 18)), (8, (8, 3, 21))])
def test_paged_attention_kernel_matches_ref(kw, bs, ctxs, hbm):
    """Both lowerings — the VMEM-staged pool and the HBM-resident one
    (pages double-buffered in via async copies) — against the oracle."""
    q, kp, vp, bt, ctx = _paged_case(3, 4, 2, 16, bs, ctxs, n_pages=16)
    o = ops.paged_attention(q, kp, vp, bt, ctx, hbm=hbm, **kw)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


def test_paged_attention_hbm_bf16_pool_and_single_page_context():
    """The HBM lowering at the serving dtype (bf16 pool) and at the
    single-page boundary (no double-buffer handoff at all)."""
    q, kp, vp, bt, ctx = _paged_case(2, 4, 2, 16, 4, (3, 4), n_pages=8)
    kp16, vp16 = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    o = ops.paged_attention(q, kp16, vp16, bt, ctx, hbm=True)
    r = ref.paged_attention_ref(q, kp16.astype(jnp.float32),
                                vp16.astype(jnp.float32), bt, ctx)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-2)


def test_paged_attention_hbm_zero_context_and_unbacked_page():
    """HBM lowering edge cases: ctx == 0 rows are all-masked zeros, and a
    -1 table entry inside the context masks instead of attending the
    clipped page."""
    q, kp, vp, bt, _ = _paged_case(2, 2, 1, 8, 4, (4, 8), n_pages=6)
    ctx = jnp.asarray([0, 8], jnp.int32)
    o = ops.paged_attention(q, kp, vp, bt, ctx, hbm=True)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    assert np.abs(np.asarray(o)[0]).max() == 0.0
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    bt2 = jnp.asarray([[-1, 2]], jnp.int32)
    ctx2 = jnp.asarray([8], jnp.int32)
    o2 = ops.paged_attention(q[:1], kp, vp, bt2, ctx2, hbm=True)
    r2 = ref.paged_attention_ref(q[:1], kp, vp, bt2, ctx2)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), atol=1e-5)


def test_paged_attention_matches_contiguous_flash_decode():
    """Ground truth: the paged gather over shuffled pages must equal plain
    single-query attention over the contiguous K/V it represents."""
    B, H, KH, D, bs = 2, 4, 2, 16, 4
    ctxs = (11, 18)
    q, kp, vp, bt, ctx = _paged_case(B, H, KH, D, bs, ctxs, n_pages=12)
    o = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    kp_n, vp_n, bt_n = map(np.asarray, (kp, vp, bt))
    for b, c in enumerate(ctxs):
        nb = -(-c // bs)
        ks = np.concatenate([kp_n[bt_n[b, j]] for j in range(nb)])[:c]
        vs = np.concatenate([vp_n[bt_n[b, j]] for j in range(nb)])[:c]
        # one query at position c-1 against its full causal context
        r = ref.flash_attention_ref(
            np.asarray(q)[b][None, None],            # [1,1,H,D]
            ks[None], vs[None], causal=False)[0, 0]
        np.testing.assert_allclose(np.asarray(o)[b], r, atol=1e-5)


def test_paged_attention_zero_context_rows_are_zero():
    q, kp, vp, bt, _ = _paged_case(2, 2, 1, 8, 4, (4, 8), n_pages=6)
    ctx = jnp.asarray([0, 8], jnp.int32)
    o = ops.paged_attention(q, kp, vp, bt, ctx)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    assert np.abs(np.asarray(o)[0]).max() == 0.0
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


def _grouped_case(H, KH):
    """Rows of context 1, 130, 300 and 0 over 16-token pages, so spans of
    128 tokens are crossed and end mid-span and mid-page, with a -1
    entry inside the 300 row's second span.  Each KV head carries its
    own offset, so a query head that attends another head's group
    reads the wrong values."""
    q, kp, vp, bt, ctx = _paged_case(4, H, KH, 16, 16, (1, 130, 300, 0),
                                     n_pages=32, seed=KH)
    head = jnp.arange(KH, dtype=jnp.float32)[None, None, :, None]
    return q, kp + 0.5 * head, vp + head, bt.at[2, 10].set(-1), ctx


@pytest.mark.parametrize("num_splits", [1, 3])
@pytest.mark.parametrize("hbm", [False, True])
@pytest.mark.parametrize("H,KH", [(12, 2), (8, 8), (4, 1)],
                         ids=["gqa", "mha", "mqa"])
def test_paged_attention_all_heads_of_a_row_per_cell(H, KH, hbm,
                                                     num_splits):
    """One grid cell serves every query head of its row, scoring each
    span against all KV heads at once and masking other groups'
    columns: GQA, MHA and MQA groupings, both lowerings, unsplit and
    split, against the oracle."""
    q, kp, vp, bt, ctx = _grouped_case(H, KH)
    o = ops.paged_attention(q, kp, vp, bt, ctx, hbm=hbm,
                            num_splits=num_splits)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    assert np.abs(np.asarray(o)[3]).max() == 0.0
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5,
                               rtol=1e-5)


def test_paged_attention_all_heads_window_and_softcap():
    """The window and softcap rules over spans, per query head."""
    q, kp, vp, bt, ctx = _grouped_case(12, 2)
    kw = dict(window=150, softcap=4.0)
    o = ops.paged_attention(q, kp, vp, bt, ctx, hbm=True, num_splits=3,
                            **kw)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# split-KV flash decoding: every split factor must be invisible to the caller
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hbm", [False, True])
@pytest.mark.parametrize("kw", [dict(), dict(window=5), dict(softcap=8.0),
                                dict(window=3, softcap=4.0)])
@pytest.mark.parametrize("ns", [2, 3, 5])
def test_paged_attention_split_matches_unsplit_and_ref(kw, ns, hbm):
    """Both lowerings, ragged contexts not divisible by num_splits, GQA
    (H=4 over KH=2): the two-pass log-sum-exp merge must reproduce the
    unsplit kernel and the oracle."""
    q, kp, vp, bt, ctx = _paged_case(3, 4, 2, 16, 4, (1, 7, 18), n_pages=16)
    o_split = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=ns,
                                  hbm=hbm, **kw)
    o_unsplit = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=1,
                                    hbm=hbm, **kw)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx, **kw)
    np.testing.assert_allclose(np.asarray(o_split), np.asarray(o_unsplit),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_split), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("hbm", [False, True])
def test_paged_attention_more_splits_than_pages(hbm):
    """num_splits > n_valid_pages: surplus splits get empty [lo, hi)
    ranges and must contribute identity partials (zero merge weight),
    not NaNs or garbage."""
    q, kp, vp, bt, ctx = _paged_case(2, 2, 1, 8, 4, (3, 8), n_pages=6)
    o = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=16, hbm=hbm)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("hbm", [False, True])
def test_paged_attention_split_zero_ctx_and_unbacked_page(hbm):
    """Split path edge cases: a ctx == 0 row stays all-zero after the
    merge, and a -1 block-table entry inside the context masks its
    positions in whichever split owns that page."""
    q, kp, vp, bt, _ = _paged_case(2, 2, 1, 8, 4, (4, 8), n_pages=6)
    ctx = jnp.asarray([0, 8], jnp.int32)
    o = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=2, hbm=hbm)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    assert np.abs(np.asarray(o)[0]).max() == 0.0
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    bt2 = jnp.asarray([[-1, 2]], jnp.int32)
    ctx2 = jnp.asarray([8], jnp.int32)
    o2 = ops.paged_attention(q[:1], kp, vp, bt2, ctx2, num_splits=2, hbm=hbm)
    r2 = ref.paged_attention_ref(q[:1], kp, vp, bt2, ctx2)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(ctx0=st.integers(0, 40), ctx1=st.integers(1, 40),
       ns=st.integers(1, 12), bs=st.sampled_from([4, 8]),
       window=st.sampled_from([None, 5]),
       softcap=st.sampled_from([None, 8.0]))
def test_paged_attention_split_equivalence_property(ctx0, ctx1, ns, bs,
                                                    window, softcap):
    """Property: ANY (context lengths, block size, split factor, masking
    flags) combination — ragged contexts, splits exceeding the page
    count, GQA heads — yields split == unsplit == ref, and identical
    greedy argmax decisions."""
    seed = ctx0 * 9973 + ctx1 * 389 + ns * 31 + bs
    n_pages = -(-max(ctx0, 1) // bs) + -(-ctx1 // bs) + 2
    q, kp, vp, bt, ctx = _paged_case(2, 4, 2, 16, bs, (ctx0, ctx1),
                                     n_pages=n_pages, seed=seed)
    kw = {}
    if window is not None:
        kw["window"] = window
    if softcap is not None:
        kw["softcap"] = softcap
    o_split = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=ns, **kw)
    o_unsplit = ops.paged_attention(q, kp, vp, bt, ctx, num_splits=1, **kw)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx, **kw)
    np.testing.assert_allclose(np.asarray(o_split), np.asarray(o_unsplit),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_split), np.asarray(r), atol=1e-5)
    # the serving gate: greedy decisions downstream of the kernel must
    # not depend on the split factor
    rng = np.random.default_rng(seed)
    readout = rng.normal(size=(np.asarray(q).shape[1] * 16, 64))
    ids = lambda o: np.argmax(np.asarray(o).reshape(2, -1) @ readout, -1)  # noqa: E731
    np.testing.assert_array_equal(ids(o_split), ids(o_unsplit))


def test_paged_attention_unbacked_page_inside_context_is_masked():
    """Regression: a -1 block-table entry WITHIN the context range must
    mask its positions (the kernel used to clip it to page 0 and attend
    that page's unrelated K/V; the ref always masked)."""
    q, kp, vp, _, _ = _paged_case(1, 2, 1, 8, 4, (8,), n_pages=6)
    bt = jnp.asarray([[-1, 2]], jnp.int32)
    ctx = jnp.asarray([8], jnp.int32)
    o = ops.paged_attention(q, kp, vp, bt, ctx)
    r = ref.paged_attention_ref(q, kp, vp, bt, ctx)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    # and only page 2's positions contribute: equal to ctx starting there
    o2 = ops.paged_attention(q, kp, vp, jnp.asarray([[2]], jnp.int32),
                             jnp.asarray([4], jnp.int32))
    # positions differ (4..7 vs 0..3) but with no window/rope the scores
    # depend only on content, so outputs match
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=1e-5)
