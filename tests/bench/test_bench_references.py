"""A configuration names its plain reference, a module found by name.

The dense module gives the same bits as the reference it was moved out
of: its weights, the program's copy of them and the gaps on one fixed
sequence are pinned below, values read before the move at the tiny
cell's sizes.  And a whole run compares against the module the
configuration names, even one that exists only as a new file."""
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest
from conftest import TINY_MODEL, tiny_cell

from bench import harness, program, spec
from bench.reference import seed_key, served_gaps
from bench.references import dense_gqa

SEED = 2 ** 33 + 5

REFERENCE_LEAVES = {
    "['embed']": "9ce0ce33d8a2bd1c", "['unembed']": "ff1c5960c231b1db",
    "['ln_f']": "72ece9c3efe45873",
    "['layers'][0]['ln1']": "013572d1eea70564",
    "['layers'][0]['ln2']": "ac0a0e0df61307d8",
    "['layers'][0]['wq']": "0a57ae37784634d6",
    "['layers'][0]['wk']": "361290dd57361710",
    "['layers'][0]['wv']": "3588b6472a4a068e",
    "['layers'][0]['wo']": "76dbcd23aff181b9",
    "['layers'][0]['w_gate']": "475de9630012c19d",
    "['layers'][0]['w_up']": "cd70cc79773df724",
    "['layers'][0]['w_down']": "d7f632296768fb90",
    "['layers'][1]['ln1']": "8dd28b15c9bf454b",
    "['layers'][1]['ln2']": "c22d08b388e83044",
    "['layers'][1]['wq']": "0afce6e6bab13ca0",
    "['layers'][1]['wk']": "e76219ef93bc88e1",
    "['layers'][1]['wv']": "7224eef8383f49a2",
    "['layers'][1]['wo']": "393b3f8209af2d88",
    "['layers'][1]['w_gate']": "44d3457b8401e6a7",
    "['layers'][1]['w_up']": "be08f69f3f7bf964",
    "['layers'][1]['w_down']": "61edd772c77aecd1"}

PROGRAM_LEAVES = {
    "['embed']['table']": "9ce0ce33d8a2bd1c",
    "['embed']['unembed']": "ff1c5960c231b1db",
    "['ln_f']['scale']": "692f72b19dabb86f",
    "['layers']['ln1']['scale']": "778695c4fa388f4b",
    "['layers']['ln2']['scale']": "024c93dca84aa8b6",
    "['layers']['attn']['wq']": "b0046370102a7172",
    "['layers']['attn']['wk']": "84a0c91fc9e69af8",
    "['layers']['attn']['wv']": "a4b8f10e99011b4f",
    "['layers']['attn']['wo']": "cce7a8510d7002cf",
    "['layers']['ffn']['w_gate']": "e06e675647f1feb6",
    "['layers']['ffn']['w_up']": "e325e71359b83c80",
    "['layers']['ffn']['w_down']": "f321bc159caea57c"}

GAPS = [0.30664363503456116, 0.5119258761405945, 0.3987637162208557,
        0.5412566065788269, 0.36406511068344116, 0.4497549533843994,
        0.9171109199523926, 0.5763201713562012]
CONTROL_GAPS = [0.005420595407485962, 0.010244607925415039, 0.0, 0.0,
                0.0, 0.0, 0.0, 0.0]


def _digest(tree):
    """The first 16 hex digits of the sha256 of each leaf's bytes."""
    return {jax.tree_util.keystr(path):
            hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def dims():
    return dense_gqa.model_dims(TINY_MODEL)


@pytest.fixture(scope="module")
def ref_weights(dims):
    return jax.jit(dense_gqa.weights, static_argnums=1)(seed_key(SEED), dims)


def test_reference_leaves_are_pinned(ref_weights):
    assert _digest(ref_weights) == REFERENCE_LEAVES


def test_program_params_are_pinned(dims):
    config = {"repro_config": "internlm2-20b", "model": TINY_MODEL,
              "program": {"use_pallas": True}}
    model = program.build(config, dense_gqa)
    params = program.make_params(model, seed_key(SEED), dims, dense_gqa)
    assert _digest(params) == PROGRAM_LEAVES


def test_served_gaps_are_pinned(dims, ref_weights):
    rng = np.random.default_rng(2 ** 31 + 16)
    tokens = rng.integers(0, dims.vocab, 64).astype(np.int32)
    at = np.arange(39, 47, dtype=np.int32)
    served = rng.integers(0, dims.vocab, 8).astype(np.int32)
    gap, gap_c = served_gaps(ref_weights, tokens, at, served,
                             hidden=dense_gqa.hidden, dims=dims, block=16,
                             control=True)
    assert np.asarray(gap).tolist() == GAPS
    assert np.asarray(gap_c).tolist() == CONTROL_GAPS


# appended to a copy of dense_gqa.py: the same module with every RMSNorm
# weight dropped from its forward pass, the program's copy untouched
NO_NORM_WEIGHT = '''

_hidden = hidden


def hidden(w, tokens, dims, block, quant=None):
    """The forward pass with every RMSNorm weight taken as 1."""
    one = jnp.ones_like
    w = {**w, "ln_f": one(w["ln_f"]),
         "layers": [{**lw, "ln1": one(lw["ln1"]), "ln2": one(lw["ln2"])}
                    for lw in w["layers"]]}
    return _hidden(w, tokens, dims, block, quant)
'''


@pytest.mark.parametrize("reference,correct", [
    ("dense_gqa", True), ("dense_gqa_no_norm_weight", False)])
def test_run_compares_against_the_reference_its_config_names(
        tmp_path, reference, correct):
    """A tree with a new cell whose configuration names its reference:
    found by name, run through the harness on the CPU, and judged
    against that module.  Nothing under ``bench/`` is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "references" / "dense_gqa_no_norm_weight.py").write_text(
        (bench / "references" / "dense_gqa.py").read_text()
        + NO_NORM_WEIGHT)
    tiny = tiny_cell(rate_rps=80.0, sample_tokens=300, sample_requests=16)
    config = dict(tiny.config, reference=reference)
    workload = dict(tiny.workload, config="tiny", traffic="tiny")
    for path, obj in (("configs/tiny.json", config),
                      ("traffic/tiny.json", tiny.mix),
                      ("workloads/tiny.new.json", workload)):
        (bench / path).write_text(json.dumps(obj))
    benchmark = {"workloads": [{"name": "tiny.new", "config": "tiny",
                                "traffic": "tiny", "chips": 1}],
                 "end_to_end": tiny.end_to_end, "per_layer": []}
    cell = spec.load_cell("tiny.new", benchmark, bench)
    assert cell.reference.__file__ == str(
        bench / "references" / f"{reference}.py")
    out = harness.run_cell(cell, 2 ** 31 + 80, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False)
    assert out["correct"] is correct, out["checks"]
    assert out["checks"]["bad_requests"]["value"] == 0
