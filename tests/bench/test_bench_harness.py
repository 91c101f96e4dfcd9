"""A whole run on the CPU at a tiny size, without the look for a chip:
sound, it comes out correct; with the timed path broken underneath in
each way a one-chip serving cell can break, it does not.  And the fp8
control reads well above the program on the same served tokens."""
import dataclasses
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, tiny_cell

from bench import harness, program
from bench.reference import seed_key
from bench.references import dense_gqa
from bench.references.dense_gqa import LAYER_LEAVES, model_dims, weight


def _run(cell, seed, **kw):
    return harness.run_cell(cell, seed, 2.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def _wrap(model, fn):
    step = model.decode_step

    def broken(params, cache, tokens, pos, block_tables=None):
        nxt, new = step(params, cache, tokens, pos, block_tables)
        return fn(nxt, tokens, cache, new)
    return dataclasses.replace(model, decode_step=broken)


FAULTS = {
    # the step leaves the KV pool as it was: nothing is written
    "state_unchanged": lambda m: _wrap(
        m, lambda nxt, toks, old, new: (nxt, old)),
    # the upper half of the batch is left out: those rows echo their input
    "half_batch_left_out": lambda m: _wrap(
        m, lambda nxt, toks, old, new: (jnp.where(
            jnp.arange(toks.shape[0]) < max(toks.shape[0] // 2, 1),
            nxt, toks[:, 0]), new)),
    # a token altered where it is produced
    "token_altered": lambda m: _wrap(
        m, lambda nxt, toks, old, new: ((nxt + 1) % m.cfg.vocab_size, new)),
}


@pytest.fixture(scope="module")
def wide_sample():
    """Enough load that every row of the batch serves, and a sample
    wide enough to hold requests of every row."""
    return tiny_cell(rate_rps=80.0, sample_tokens=300, sample_requests=16)


def test_sound_run_is_correct(wide_sample):
    out = _run(wide_sample, 2 ** 31 + 77)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["bad_requests"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(wide_sample, fault):
    out = _run(wide_sample, 2 ** 31 + 78, fault=FAULTS[fault])
    assert not out["correct"], out["checks"]


def test_fp8_control_reads_far_above_the_program(wide_sample):
    out = _run(wide_sample, 2 ** 31 + 79, control=True)
    prog = out["program_max_logit_gap"]
    ctrl = out["checks"]["max_logit_gap"]["value"]
    assert ctrl > max(3 * prog, 1e-3), (prog, ctrl)
    # in the program's place, the control fails the harness's own check
    assert not out["correct"], out["checks"]
    assert prog <= out["checks"]["max_logit_gap"]["limit"]


def test_program_weights_are_the_reference_weights():
    from conftest import TINY_MODEL
    config = {"repro_config": "internlm2-20b", "model": TINY_MODEL,
              "program": {"use_pallas": True}}
    dims = model_dims(TINY_MODEL)
    model = program.build(config, dense_gqa)
    key = seed_key(2 ** 33 + 5)
    p = program.make_params(model, key, dims, dense_gqa)
    for i in range(dims.n_layers):
        np.testing.assert_allclose(
            p["layers"]["attn"]["wq"][i], weight(key, "wq", i, dims),
            rtol=1e-6)
        np.testing.assert_allclose(
            1.0 + p["layers"]["ln2"]["scale"][i], weight(key, "ln2", i, dims),
            rtol=1e-6)
    np.testing.assert_allclose(p["embed"]["unembed"],
                               weight(key, "unembed", 0, dims), rtol=1e-6)
    assert set(LAYER_LEAVES) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                 "w_gate", "w_up", "w_down"}


def _bench_cmd(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-20b.chat",
         "--seed", "1", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_refuses_a_machine_without_a_tpu():
    r = _bench_cmd(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_run_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _bench_cmd(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
