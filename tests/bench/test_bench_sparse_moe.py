"""The sparse-expert family: its plain reference, the program against it,
and the two readers of the expert layer's kernel.

The reference (``bench/references/sparse_moe.py``) imports nothing of
the program.  At a tiny size on the CPU, with seeded random weights:
four chips' shares of a layer add up to the uncut layer; prefill then
decode through the paged pool gives the reference's logits; a whole run
of a tiny cell comes out correct, and the fp8 control does not."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, program, spec
from bench.metrics import expert_ffn_ms, expert_ffn_roofline
from bench.reference import seed_key
from bench.references import sparse_moe
from bench.serve_loop import STEP
from bench.trace import Event, TraceSummary

SEED = 2 ** 33 + 18

# 16 router outputs, top 4, 4 experts held: shard 1 of 4
TINY_MOE = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 32, "num_experts": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": False, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "expert_shards": 4, "expert_shard": 1}


def _model(**over):
    return dict(TINY_MOE, **over)


def _config(model, **prog):
    return {"name": "tiny-moe", "repro_config": "olmoe-1b-7b",
            "reference": "sparse_moe", "model": model, "source": "test",
            "reduced": {}, "deployment": "test",
            "program": {"use_pallas": True, **prog}}


def _layer_weights(key, dims, layer=0):
    return {n: sparse_moe.weight(key, n, layer, dims)
            for n in sparse_moe.LAYER_LEAVES} | {
        n: sparse_moe.held_experts(key, n, layer, dims)
        for n in sparse_moe.EXPERT_LEAVES}


# ---------------------------------------------------------------------------
# (a) one chip's share


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_four_shares_add_up_to_the_uncut_reference_layer(norm_topk_prob):
    """What the four chips' experts give, summed, is the whole layer's
    expert output: each share routes over all 16 experts and keeps its
    own 4, whose weights depend on the expert's global index alone."""
    key = seed_key(SEED)
    whole = sparse_moe.model_dims(_model(num_experts=16, expert_shards=1,
                                         expert_shard=0,
                                         norm_topk_prob=norm_topk_prob))
    h = jax.random.normal(jax.random.PRNGKey(3), (24, whole.d_model))
    want = sparse_moe.expert_mix(_layer_weights(key, whole), h, whole)
    got = 0.0
    for shard in range(4):
        dims = sparse_moe.model_dims(_model(expert_shard=shard,
                                            norm_topk_prob=norm_topk_prob))
        got = got + sparse_moe.expert_mix(_layer_weights(key, dims), h, dims)
    # float32 sums of the same products in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_program_shares_add_up_to_the_uncut_reference_layer():
    """The program's expert layer, given each chip's share of the same
    weights (the benchmark's own layout map), adds up over the four
    shares to the uncut reference layer."""
    from repro.models.layers import moe
    key = seed_key(SEED)
    whole = sparse_moe.model_dims(_model(num_experts=16, expert_shards=1,
                                         expert_shard=0))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 12, whole.d_model))
    want = sparse_moe.expert_mix(_layer_weights(key, whole),
                                 h.reshape(-1, whole.d_model), whole)
    got = 0.0
    for shard in range(4):
        config = _config(_model(expert_shard=shard),
                         compute_dtype="float32")
        dims = sparse_moe.model_dims(config["model"])
        model = program.build(config, sparse_moe)
        p = program.make_params(model, key, dims, sparse_moe)
        ffn = jax.tree.map(lambda a: a[0], p["layers"]["ffn"])
        out, counts = moe.expert_layer(ffn, h, model.cfg)
        got = got + out.reshape(-1, whole.d_model)
    # float32 on both sides; the router's top-k picks are the same
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# (b) prefill, then decode, through the paged pool


def _reference_logits(key, dims, tokens):
    w = jax.jit(sparse_moe.weights, static_argnums=1)(key, dims)
    x = sparse_moe.hidden(w, jnp.asarray(tokens), dims, len(tokens))
    return np.asarray(x @ w["unembed"])


@pytest.mark.parametrize("compute_dtype,atol", [
    # float32 end to end, the pool too: only the order of sums differs
    # (2.4e-7 read here); a wrong equation (per-head QK-norm, gates
    # renormalised) moves these logits, up to 0.57, by far more
    ("float32", 1e-5),
    # the engine's bfloat16 activations, matmul inputs and KV pool: 8
    # bits of mantissa at every layer, compounding (4.4e-3 read here of
    # logits up to 0.57); 2e-2 leaves a margin of 4.5x
    ("bfloat16", 2e-2)])
def test_paged_prefill_then_decode_matches_the_reference(compute_dtype,
                                                         atol):
    key = seed_key(SEED + 1)
    config = _config(_model(), compute_dtype=compute_dtype)
    dims = sparse_moe.model_dims(config["model"])
    model = program.build(config, sparse_moe)
    params = program.make_params(model, key, dims, sparse_moe)
    C, bs, n_dec = 8, 4, 6
    prompt = np.random.default_rng(0).integers(0, dims.vocab, 13)
    tokens = list(prompt)
    bt = jnp.arange(8, dtype=jnp.int32)[None]           # 32 positions
    # the pool in the compute dtype: float32 keys and values leave only
    # the order of sums to tell the program from the reference
    cache = jax.tree.map(lambda a: a.astype(compute_dtype),
                         model.init_paged_cache(8, bs))
    got, at = [], []
    for end in (C, len(prompt)):                        # two chunks
        start = end - C                                 # the last overlaps
        chunk = jnp.asarray(prompt[start:end], jnp.int32)[None]
        logits, cache = model.decode(params, cache, chunk,
                                     jnp.asarray([start], jnp.int32), bt)
        got.append(np.asarray(logits[0]))
        at.append(end - 1)
    for _ in range(n_dec):
        nxt = int(np.argmax(got[-1]))
        tokens.append(nxt)
        pos = len(tokens) - 1
        logits, cache = model.decode(params, cache,
                                     jnp.asarray([[nxt]], jnp.int32),
                                     jnp.asarray([pos], jnp.int32), bt)
        got.append(np.asarray(logits[0]))
        at.append(pos)
    ref = _reference_logits(key, dims, np.asarray(tokens, np.int32))
    np.testing.assert_allclose(np.stack(got, 0).astype(np.float32),
                               ref[np.asarray(at)], atol=atol, rtol=0)


def _tiny_cell(**check):
    config = _config(_model())
    mix = {"generator": "open_loop", "arrivals": {"process": "poisson"},
           "prompt_tokens": {"dist": "lognormal", "median": 20,
                             "sigma": 0.6, "min": 4, "max": 60},
           "output_tokens": {"dist": "lognormal", "median": 8,
                             "sigma": 0.5, "min": 2, "max": 16}}
    workload = {"config": "tiny-moe", "traffic": "tiny", "chips": 1,
                "rate_rps": 80.0, "lead_s": 0.5, "grace_s": 10.0,
                "engine": {"max_batch": 4, "max_len": 80, "block_size": 8,
                           "chunk_size": 16, "n_blocks": 40,
                           "compact_on_retire": False},
                "check": {"max_logit_gap": 0.01, "sample_tokens": 300,
                          "sample_requests": 16, **check},
                "why": "test"}
    e2e = [{"name": "output_tok_s", "unit": "tokens/s"}]
    return spec.Cell("tiny-moe.test", workload, config, mix, e2e, [])


@pytest.fixture(scope="module")
def tiny_moe_cell():
    return _tiny_cell()


def test_sound_run_of_a_sparse_cell_is_correct(tiny_moe_cell):
    """The whole harness on the tiny sparse cell: the engine's fused
    steps against ``sparse_moe``, under the dense tiny cell's limit."""
    out = harness.run_cell(tiny_moe_cell, 2 ** 31 + 91, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0


def test_fp8_control_fails_the_sparse_cell(tiny_moe_cell):
    out = harness.run_cell(tiny_moe_cell, 2 ** 31 + 92, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           control=True)
    prog = out["program_max_logit_gap"]
    ctrl = out["checks"]["max_logit_gap"]["value"]
    assert ctrl > max(3 * prog, 1e-3), (prog, ctrl)
    assert not out["correct"], out["checks"]


def test_the_configuration_file_maps_to_the_published_model():
    """The committed configuration: published widths, 16 of 64 experts
    held (shard 0 of 4), 4 of 16 layers, and the program built from it
    is OLMoE's: full-width QK-norm, no top-k renormalisation."""
    config = spec.load_config("olmoe-1b-7b")
    dims = sparse_moe.model_dims(config["model"])
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            dims.d_ff, dims.vocab) == (2048, 16, 16, 128, 1024, 50304)
    assert (dims.n_experts, dims.top_k, dims.n_held, dims.first_held) == (
        64, 8, 16, 0)
    assert config["published"] == {"num_hidden_layers": 16,
                                   "num_experts": 64}
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers"]
    cfg = program.model_cfg(config, sparse_moe)
    assert (cfg.n_layers, cfg.qk_norm, cfg.qk_norm_width, cfg.norm_eps) == (
        4, True, "full", 1e-5)
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.first_held,
            cfg.moe.norm_topk_prob) == (64, 16, 0, False)
    # attention, router, a quarter of top-8's expert work, every layer
    assert dims.trunk_flops() == 2 * 4 * (
        4 * 2048 * 2048 + 2048 * 64 + 2 * 3 * 2048 * 1024)


# ---------------------------------------------------------------------------
# the readers of the expert layer's kernel

DIMS = sparse_moe.model_dims({
    **TINY_MOE, "num_hidden_layers": 4, "hidden_size": 2048,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "intermediate_size": 1024, "num_experts": 16,
    "num_experts_per_tok": 8, "vocab_size": 50304, "expert_shard": 0})
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_expert_work_is_pinned_on_fixed_shapes():
    # a 64-row decode: 128 held pairs; all 16 held experts touched
    f, b = expert_ffn_roofline.launch_work(64, DIMS)
    assert f == 6 * 2048 * 1024 * 128
    touched = 16 * (1 - (1 - 8 / 64) ** 64)
    assert b == pytest.approx(touched * 3 * 2048 * 1024 * 2
                              + 2 * 64 * 2048 * 2)
    assert touched == pytest.approx(16 * 0.99980, rel=1e-4)
    # one token touches 8/64 of the held experts: 2 of them
    f1, b1 = expert_ffn_roofline.launch_work(1, DIMS)
    assert f1 == 6 * 2048 * 1024 * 2
    assert b1 == pytest.approx(2 * 3 * 2048 * 1024 * 2 + 2 * 2048 * 2)
    # bandwidth bounds both; four layers
    assert expert_ffn_roofline.launch_bound_s(64, DIMS, PEAKS) == \
        pytest.approx(4 * b / 819e9)
    f256, b256 = expert_ffn_roofline.launch_work(256, DIMS)
    assert f256 == 6 * 2048 * 1024 * 512


class _Req:
    def __init__(self, prompt_len, token_t):
        self.prompt = np.zeros(prompt_len, np.int32)
        self.token_t = token_t


def _run(ops, host=(), requests=()):
    cell = _tiny_cell()
    cell = dataclasses.replace(cell, workload=dict(
        cell.workload, engine=dict(cell.workload["engine"], chunk_size=256)))
    tr = TraceSummary(window=(0.0, 10.0), n_devices=1, ops=[list(ops)],
                      modules=[[]], host=list(host))
    rec = harness.Records(t0=0.0, t1=10.0, t_end=10.0,
                          requests=list(requests), steps=[], lateness=[])
    return harness.RunData(cell, DIMS, PEAKS, 1, 0.0, rec, tr)


# decode steps of 3, 2 and 1 rows; a 600-token prompt (256, 256, 88)
REQUESTS = [_Req(600, [1.0, 2.0, 3.0]), _Req(40, [-1.0, 2.0, 3.0]),
            _Req(40, [-1.0, 2.0, 5.0])]


@pytest.mark.parametrize("name", ["%_gmm_jit.3", "%ragged-dot-none.2",
                                  "%ragged-dot-metadata.1"])
def test_roofline_reads_the_same_whichever_kernel_does_the_work(name):
    ops = [Event(f"{name} = f32[512,1024] custom-call(%a, %b)", 1.0, 1.5),
           Event(f"{name}.7 = f32[512,2048] custom-call(%c, %d)", 4.0, 4.5),
           # a consumer of the kernel's output is not the kernel
           Event("%fusion.9 = bf16[512,1024] fusion(%ragged-dot-none.1)",
                 2.0, 3.0)]
    steps = [Event(STEP, t, t + 0.5) for t in (0.5, 1.5, 2.5, 3.5)]
    run = _run(ops, steps, REQUESTS)
    sizes = sorted(expert_ffn_roofline.launches(run))
    assert sizes == [1, 2, 3, 88, 256, 256]
    bound = sum(expert_ffn_roofline.launch_bound_s(T, DIMS, PEAKS)
                for T in sizes)
    assert expert_ffn_roofline.read(run) == pytest.approx(100 * bound / 1.0)
    # one second of kernel time over four steps begun in the window
    assert expert_ffn_ms.read(run) == pytest.approx(250.0)


def test_readers_find_nothing_without_the_kernel_or_a_chip():
    run = _run([Event("%fusion.1 = f32[8] fusion(%x)", 1.0, 2.0)],
               [Event(STEP, 0.5, 1.0)], REQUESTS)
    assert expert_ffn_roofline.read(run) is None
    assert expert_ffn_ms.read(run) is None
    cpu = dataclasses.replace(run, peaks=None)
    assert expert_ffn_roofline.read(cpu) is None
    assert expert_ffn_ms.read(cpu) is None
