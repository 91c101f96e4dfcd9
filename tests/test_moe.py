"""MoE routing invariants — unit + hypothesis property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade: property tests skip, unit tests still run
    from _hypothesis_stub import given, settings, st

from repro.configs import ARCHS, reduced
from repro.models.layers import moe as M


def _cfg(E=4, K=2, cf=1.0, shared=0):
    cfg = reduced(ARCHS["olmoe-1b-7b"])
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, n_experts=E, top_k=K, capacity_factor=cf, n_shared=shared))


def test_route_positions_within_capacity():
    S, E, K, C = 32, 4, 2, 8
    logits = jax.random.normal(jax.random.PRNGKey(0), (S, E))
    gates, eid, slot, keep = M._route(logits, K, C)
    slot = np.asarray(slot)
    keep = np.asarray(keep)
    assert (slot[keep] < C).all()
    # kept slots are unique per expert
    eid = np.asarray(eid)
    seen = set()
    for s in range(S):
        for k in range(K):
            if keep[s, k]:
                key = (eid[s, k], slot[s, k])
                assert key not in seen
                seen.add(key)


def test_gates_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    gates, *_ = M._route(logits, 3, 8)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-5)


def test_moe_ffn_runs_and_is_finite():
    cfg = _cfg(shared=1)
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)
                          ).astype(jnp.bfloat16)
    out, aux = M.moe_ffn(p, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert float(aux["moe_load_balance"]) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz


def test_high_capacity_matches_dense_mixture():
    """With capacity so large nothing drops, MoE == explicit per-token
    mixture of expert MLPs."""
    cfg = _cfg(E=4, K=2, cf=16.0)
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model)
                          ).astype(jnp.float32)
    out, _ = M.moe_ffn(p, x, cfg)

    logits = np.asarray(jnp.einsum("bsd,de->bse", x, p["router"]))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))[0]
    expect = np.zeros_like(np.asarray(x))[0]
    for s in range(8):
        top = np.argsort(-probs[s])[:2]
        g = probs[s][top]
        if cfg.moe.norm_topk_prob:          # OLMoE's gates: not divided
            g = g / g.sum()
        for gi, e in zip(g, top):
            xe = jnp.asarray(x[0, s:s+1][None])
            h = np.asarray(jax.nn.silu(xe @ p["w_gate"][e]) * (xe @ p["w_up"][e])
                           @ p["w_down"][e])[0, 0]
            expect[s] += gi * h
    np.testing.assert_allclose(np.asarray(out)[0], expect, atol=2e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 16), st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_route_keep_is_prefix_of_expert_arrivals(E, K, seed):
    """Property: overflow drops the LATEST arrivals (token order priority)."""
    K = min(K, E)
    S, C = 24, 8
    logits = jax.random.normal(jax.random.PRNGKey(seed % 2**31), (S, E))
    gates, eid, slot, keep = M._route(logits, K, C)
    eid, slot, keep = map(np.asarray, (eid, slot, keep))
    for e in range(E):
        arrivals = [(s, k) for s in range(S) for k in range(K)
                    if eid[s, k] == e]
        kept = [keep[s, k] for s, k in arrivals]
        # all kept arrivals precede all dropped ones
        assert kept == sorted(kept, reverse=True)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_capacity_factor_monotone_in_drops(seed):
    cfg_lo = _cfg(cf=0.25)
    cfg_hi = _cfg(cf=8.0)
    p = M.init_moe(jax.random.PRNGKey(0), cfg_lo)
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**31),
                          (1, 32, cfg_lo.d_model)).astype(jnp.float32)
    out_lo, _ = M.moe_ffn(p, x, cfg_lo)
    out_hi, _ = M.moe_ffn(p, x, cfg_hi)
    # low capacity zeroes some tokens' routed output -> smaller norm
    assert (np.linalg.norm(np.asarray(out_lo))
            <= np.linalg.norm(np.asarray(out_hi)) + 1e-3)


# ---------------------------------------------------------------------------
# the serving layer: dropless over the experts this chip holds


def _held_cfg(E=8, K=2, shards=1, shard=0, norm=True):
    cfg = reduced(ARCHS["olmoe-1b-7b"])
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, n_experts=E, top_k=K, norm_topk_prob=norm,
        expert_shards=shards, expert_shard=shard))


def _mixture(p, x, cfg):
    """The explicit per-token mixture, in numpy: every token through its
    top-k experts that this chip holds, weighted by its gates."""
    m = cfg.moe
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(len(x)):
        top = np.argsort(-probs[t], kind="stable")[:m.top_k]
        g = probs[t][top]
        if m.norm_topk_prob:
            g = g / g.sum()
        for gi, e in zip(g, top):
            j = e - m.first_held
            if not 0 <= j < m.n_held:
                continue
            wg, wu, wd = (np.asarray(p[n][j], np.float64)
                          for n in ("w_gate", "w_up", "w_down"))
            a = x[t] @ wg
            out[t] += gi * ((a / (1 + np.exp(-a))) * (x[t] @ wu)) @ wd
    return out


def _loads(p, x, cfg):
    logits = np.asarray(x, np.float64).reshape(-1, cfg.d_model) \
        @ np.asarray(p["router"], np.float64)
    top = np.argsort(-logits, -1, kind="stable")[:, :cfg.moe.top_k]
    return np.bincount(top.reshape(-1), minlength=cfg.moe.n_experts)


@pytest.mark.parametrize("norm", [False, True])
def test_expert_layer_equals_the_explicit_mixture(norm):
    """``norm_topk_prob`` false (OLMoE) keeps the softmax gates as they
    are; true divides them by their sum.  Either way the layer is the
    per-token mixture of its held experts."""
    cfg = _held_cfg(norm=norm)
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 8, cfg.d_model))
    out, counts = M.expert_layer(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model),
                               _mixture(p, x, cfg), rtol=1e-4, atol=1e-5)
    assert int(counts[M.EXPERT_PAIRS]) == 3 * 8 * 2


def test_expert_layer_is_dropless_when_every_token_picks_one_expert():
    """All tokens route to expert 5: it gets every token of the launch,
    far past any capacity, and none is dropped."""
    cfg = _held_cfg()
    p = M.init_moe(jax.random.PRNGKey(1), cfg)
    u = jnp.ones(cfg.d_model) / cfg.d_model ** 0.5
    x = 2.0 * u + 0.5 * jax.random.normal(jax.random.PRNGKey(6),
                                          (4, 16, cfg.d_model))
    p["router"] = p["router"].at[:, 5].set(20.0 * u)
    # every token's largest logit is expert 5's
    assert (np.argmax(np.asarray(x.reshape(-1, cfg.d_model) @ p["router"]),
                      -1) == 5).all()
    out, counts = M.expert_layer(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model),
                               _mixture(p, x, cfg), rtol=1e-4, atol=1e-5)
    assert int(counts[M.EXPERT_MAX]) == 4 * 16
    # the capacity path of training drops most of them
    dropped, _ = M.moe_ffn(p, x, cfg)
    assert not np.allclose(np.asarray(dropped), np.asarray(out), atol=1e-3)


def test_expert_layer_is_dropless_in_a_256_token_chunk_under_skew():
    """A 256-token chunk in which one expert gets over twice its mean
    load: the layer still equals the explicit mixture."""
    cfg = _held_cfg(E=8, K=2)
    p = M.init_moe(jax.random.PRNGKey(2), cfg)
    u = jnp.ones(cfg.d_model) / cfg.d_model ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 256, cfg.d_model)) \
        + 2.0 * u
    p["router"] = p["router"].at[:, 3].add(0.5 * u)
    loads = _loads(p, x, cfg)
    mean = 256 * 2 / 8
    assert loads[3] > 2 * mean and loads.sum() == 512
    out, counts = M.expert_layer(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out)[0], _mixture(p, x, cfg),
                               rtol=1e-4, atol=1e-5)
    assert int(counts[M.EXPERT_MAX]) == loads.max()


def test_expert_layer_computes_only_its_shard_and_the_valid_tokens():
    """Shard 2 of 4 holds experts 4-5 of 8: only pairs routed there are
    computed (and counted); a token marked invalid routes nowhere."""
    cfg = _held_cfg(shards=4, shard=2)
    p = M.init_moe(jax.random.PRNGKey(3), cfg)
    assert p["w_gate"].shape[0] == 2 and p["router"].shape[1] == 8
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.d_model))
    valid = jnp.arange(12)[None, :] < jnp.asarray([[12], [5]])
    out, counts = M.expert_layer(p, x, cfg, valid=valid)
    want = _mixture(p, x, cfg).reshape(2, 12, -1)
    want[1, 5:] = 0.0
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)
    loads = _loads(p, x[:, :, :], cfg)
    held_valid = _loads(p, jnp.concatenate([x[0], x[1, :5]]), cfg)[4:6]
    assert int(counts[M.EXPERT_PAIRS]) == held_valid.sum() < loads.sum()


def test_engine_counts_the_expert_pairs_of_every_launch():
    """The paged engine carries each launch's counts out beside its
    tokens and books them at the step's one sync: a dense model's
    programs return no counts."""
    from repro.models.zoo import build_model
    from repro.serve.engine import PagedServingEngine, paged_decode_fn
    cfg = _held_cfg(shards=2, shard=1)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(model, params, max_batch=3, max_len=48,
                             block_size=8, n_blocks=24, chunk_size=8)
    rng = np.random.default_rng(0)
    for n in (5, 13, 20):
        eng.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=4)
    stats = eng.run_until_done()
    # 3 prompts of 38 tokens, 9 decoded: 47 tokens, 2 picks each, in 2
    # layers; about half of the picks land on this chip's 4 of 8
    assert 0 < stats.expert_pairs < 47 * 2 * 2
    assert 0 < stats.expert_max_load <= stats.expert_pairs
    dense = build_model(reduced(ARCHS["internlm2-20b"]))
    B, NB = 2, 4
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    out = jax.eval_shape(
        paged_decode_fn(dense.decode_step),
        jax.eval_shape(dense.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: dense.init_paged_cache(B * NB, 8)),
        i32(B), i32(B), i32(B, NB))
    assert out[-1] == {}


@pytest.mark.parametrize("arch,width", [("gemma3-1b", "head"),
                                        ("olmoe-1b-7b", "full")])
def test_qk_norm_over_each_head_or_the_whole_projection(arch, width):
    """Gemma 3 norms q and k over each head's width, bit for bit as
    before; OLMoE over the whole projection, all heads at once, before
    the split into heads."""
    from repro.models.layers import attention as A
    from repro.models.layers.basic import rmsnorm
    cfg = reduced(ARCHS[arch])
    assert cfg.qk_norm and cfg.qk_norm_width == width
    p = A.init_attention(jax.random.PRNGKey(0), cfg)
    H, KH, hd = cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    p["q_norm"]["scale"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), p["q_norm"]["scale"].shape)
    p["k_norm"]["scale"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["k_norm"]["scale"].shape)
    assert p["q_norm"]["scale"].shape == ((hd,) if width == "head"
                                          else (H * hd,))
    assert p["k_norm"]["scale"].shape == ((hd,) if width == "head"
                                          else (KH * hd,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.d_model))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    if width == "head":
        want_q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        want_k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    else:
        want_q = rmsnorm(p["q_norm"], q.reshape(2, 5, H * hd),
                         cfg.norm_eps).reshape(q.shape)
        want_k = rmsnorm(p["k_norm"], k.reshape(2, 5, KH * hd),
                         cfg.norm_eps).reshape(k.shape)
        # not the per-head form: each head keeps its share of the norm
        per_head = rmsnorm({"scale": p["q_norm"]["scale"][:hd]}, q,
                           cfg.norm_eps)
        assert not np.allclose(np.asarray(want_q), np.asarray(per_head),
                               atol=1e-3)
    got_k, _ = A._project_kv(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(A._project_q(p, x, cfg)),
                                  np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
