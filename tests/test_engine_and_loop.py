"""End-to-end behaviour: serving engine vs raw decode (with and without
cost-model-gated admission), and the training loop with checkpoint-restart
determinism and predicted-vs-measured step logging."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.costmodel import CostModel
from repro.launch.mesh import make_host_mesh
from repro.models.zoo import build_model
from repro.serve.engine import ServingEngine
from repro.serve.telemetry import TelemetryController
from repro.train.loop import train


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, n, max_len):
    logits, cache = model.prefill(params, {"tokens": prompt[None, :]},
                                  max_len=max_len)
    toks = [int(jnp.argmax(logits[0]))]
    pos0 = prompt.shape[0]
    for t in range(n - 1):
        lg, cache = model.decode(params, cache,
                                 jnp.asarray([[toks[-1]]], jnp.int32),
                                 jnp.asarray([pos0 + t], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_engine_matches_raw_greedy_decode(tiny_lm):
    cfg, model, params = tiny_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=48)
    prompts = [np.arange(5, 13, dtype=np.int32) % cfg.vocab_size,
               np.arange(40, 52, dtype=np.int32) % cfg.vocab_size]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_done()
    for rid, p in zip(rids, prompts):
        got = eng.done[rid].tokens
        want = _greedy_reference(model, params, jnp.asarray(p), 6, 48)
        assert got == want, (got, want)


def test_engine_queues_beyond_batch(tiny_lm):
    cfg, model, params = tiny_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    for i in range(5):
        eng.submit(np.arange(3 + i, dtype=np.int32), max_new_tokens=4)
    stats = eng.run_until_done()
    assert stats.completed == 5
    assert stats.prefills == 5
    assert all(len(r.tokens) == 4 for r in eng.done.values())


def test_engine_cost_model_admission_defers_but_completes(tiny_lm):
    """With a deliberately tight step budget the engine must stage prefill
    admissions across steps (deferrals observed) yet still finish every
    request with the same greedy tokens."""
    cfg, model, params = tiny_lm
    cm = CostModel.from_named("tpu_v5e")
    ctl = TelemetryController(drift=False)      # records only
    eng = ServingEngine(model, params, max_batch=4, max_len=48,
                        cost_model=cm, step_budget_s=0.0, telemetry=ctl)
    prompts = [np.arange(3 + i, dtype=np.int32) % cfg.vocab_size
               for i in range(6)]
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    stats = eng.run_until_done()
    assert stats.completed == 6
    assert stats.deferred_prefills > 0          # the budget actually gated
    predicted = [r.predicted_s for r in ctl.sink.steps()]
    assert len(predicted) == stats.steps
    assert all(s > 0 for s in predicted)
    for rid, p in zip(rids, prompts):
        want = _greedy_reference(model, params, jnp.asarray(p), 4, 48)
        assert eng.done[rid].tokens == want


def test_engine_cost_model_generous_budget_packs_greedily(tiny_lm):
    """A generous budget must not change the old greedy packing."""
    cfg, model, params = tiny_lm
    cm = CostModel.from_named("tpu_v5e")
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        cost_model=cm, step_budget_s=1e9)
    for i in range(5):
        eng.submit(np.arange(3 + i, dtype=np.int32), max_new_tokens=4)
    stats = eng.run_until_done()
    assert stats.completed == 5
    assert stats.deferred_prefills == 0


def test_train_logs_predicted_vs_measured(tmp_path):
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=64)
    model = build_model(cfg)
    seen = []
    res = train(model, make_host_mesh(), num_steps=3, global_batch=4,
                seq_len=16, cost_model=CostModel.from_named("tpu_v5e"),
                hooks=[lambda step, m: seen.append(m)])
    assert res.predicted_step_s is not None and res.predicted_step_s > 0
    assert len(res.step_times_s) == 3
    assert all("predicted_step_s" in m and "measured_step_s" in m
               for m in seen)
    assert seen[0]["predicted_step_s"] == res.predicted_step_s


def test_train_loss_decreases(tmp_path):
    cfg = reduced(ARCHS["gemma2-2b"], n_layers=2, vocab_size=97)
    model = build_model(cfg)
    res = train(model, make_host_mesh(), num_steps=30, global_batch=8,
                seq_len=32, lr=5e-3)
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.2, (first, last)


def test_train_restart_is_deterministic(tmp_path):
    cfg = reduced(ARCHS["internlm2-20b"], n_layers=2, vocab_size=97)
    model = build_model(cfg)
    mesh = make_host_mesh()
    kw = dict(global_batch=4, seq_len=16, lr=1e-3, seed=11)
    # one uninterrupted 10-step run
    r_full = train(model, mesh, num_steps=10, **kw)
    # 5 steps, "crash", restore, 5 more
    d = tmp_path / "ck"
    r_a = train(model, mesh, num_steps=5, ckpt_dir=str(d), ckpt_every=5, **kw)
    r_b = train(model, mesh, num_steps=10, ckpt_dir=str(d), ckpt_every=5,
                **kw)
    assert r_b.restored_from == 5
    np.testing.assert_allclose(r_full.losses[5:], r_b.losses, rtol=2e-3,
                               atol=2e-3)
