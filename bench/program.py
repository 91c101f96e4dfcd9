"""The system under test, built from a configuration file: the model the
program builds, the weights in its layout, and its paged serving
engine.  This is the only module of the benchmark that imports the
program (``repro``)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import LAYER_LEAVES, Dims, weight

# the program's ModelCfg fields each published key of a configuration
# file sets; anything else in ``program`` is passed through as is
_MODEL_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv_heads",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def model_cfg(config: dict):
    """The program's ``ModelCfg``: its named config, with every size the
    file states and the file's program settings (``use_pallas``)."""
    from repro.configs import get_config
    base = get_config(config["repro_config"])
    kw = {field: config["model"][key] for key, field in _MODEL_FIELDS.items()}
    kw["head_dim"] = kw["d_model"] // kw["n_heads"]
    kw["tie_embeddings"] = bool(config["model"]["tie_word_embeddings"])
    kw.update(config.get("program", {}))
    return dataclasses.replace(base, **kw)


def build(config: dict):
    from repro.models.zoo import build_model
    return build_model(model_cfg(config))


def _norm_scale(w):
    """The program's RMSNorm computes ``x * (1 + scale)``."""
    return {"scale": w - 1.0}


def program_params(key, dims: Dims, cfg):
    """The reference's weights in the program's parameter layout (call
    under ``jax.jit``).  Stacked layers are made under ``vmap`` of the
    same per-layer function the reference calls."""
    if cfg.padded_vocab != dims.vocab or cfg.padded_heads != dims.n_heads:
        raise ValueError("padded vocabulary or heads: not a layout this "
                         "benchmark maps")
    stack = {n: jax.vmap(lambda i, n=n: weight(key, n, i, dims))(
        jnp.arange(dims.n_layers)) for n in LAYER_LEAVES}
    return {
        "embed": {"table": weight(key, "embed", 0, dims),
                  "unembed": weight(key, "unembed", 0, dims)},
        "ln_f": _norm_scale(weight(key, "ln_f", 0, dims)),
        "pre_layers": [],
        "layers": {
            "ln1": _norm_scale(stack["ln1"]),
            "ln2": _norm_scale(stack["ln2"]),
            "attn": {n: stack[n] for n in ("wq", "wk", "wv", "wo")},
            "ffn": {n: stack[n] for n in ("w_gate", "w_up", "w_down")},
        },
    }


def make_params(model, key, dims: Dims):
    """The program's parameters, made on the device in one jitted call;
    fails if the program's layout is not the one mapped here."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: program_params(k, dims, model.cfg), key)
    if (jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.jit(lambda k: program_params(k, dims, model.cfg))(key)


def make_engine(model, params, engine: dict):
    """``PagedServingEngine`` with the fused, donated, pipelined step and
    no cost model or tuner: admission is ungated."""
    from repro.serve.engine import PagedServingEngine
    return PagedServingEngine(
        model, params, max_batch=engine["max_batch"],
        max_len=engine["max_len"], block_size=engine["block_size"],
        n_blocks=engine["n_blocks"], chunk_size=engine["chunk_size"],
        compact_on_retire=engine["compact_on_retire"], fused=True)
