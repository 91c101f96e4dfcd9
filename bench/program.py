"""The system under test, built from a configuration file: the model the
program builds, the weights in its layout, and its paged serving
engine.  This is the only module of the benchmark that imports the
program (``repro``)."""
from __future__ import annotations

import dataclasses

import jax


def model_cfg(config: dict, ref):
    """The program's ``ModelCfg``: its named config, with every size the
    file states (as the reference module ``ref`` maps them) and the
    file's program settings (``use_pallas``)."""
    from repro.configs import get_config
    base = get_config(config["repro_config"])
    kw = ref.program_fields(config["model"])
    kw.update(config.get("program", {}))
    return dataclasses.replace(base, **kw)


def build(config: dict, ref):
    from repro.models.zoo import build_model
    return build_model(model_cfg(config, ref))


def make_params(model, key, dims, ref):
    """The program's parameters, made on the device in one jitted call;
    fails if the program's layout is not the one ``ref`` maps."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(
        lambda k: ref.program_params(k, dims, model.cfg), key)
    if (jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.jit(lambda k: ref.program_params(k, dims, model.cfg))(key)


def load(cell, key):
    """A cell's sizes, the program's model and its parameters made from
    ``key``, all as the reference module its configuration names maps
    them."""
    ref = cell.reference
    dims = ref.model_dims(cell.config["model"])
    model = build(cell.config, ref)
    return dims, model, make_params(model, key, dims, ref)


def make_engine(model, params, engine: dict):
    """``PagedServingEngine`` with the fused, donated, pipelined step and
    no cost model or tuner: admission is ungated."""
    from repro.serve.engine import PagedServingEngine
    return PagedServingEngine(
        model, params, max_batch=engine["max_batch"],
        max_len=engine["max_len"], block_size=engine["block_size"],
        n_blocks=engine["n_blocks"], chunk_size=engine["chunk_size"],
        compact_on_retire=engine["compact_on_retire"], fused=True)
