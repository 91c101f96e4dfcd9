"""The plain reference of a llama-style dense GQA decoder in float32, the
weights that both it and the program under test are given, and the
program's layout of them.

It follows the published description of InternLM2 and Yi (RMSNorm with
a weight, rotary embeddings on the split halves of each head, grouped
query attention, a SwiGLU MLP, untied embedding and head) and imports
nothing of the program: it runs one whole sequence at a time, with no
cache, no paging and no batching, every matmul at ``Precision.HIGHEST``.

Weights come from the seed alone.  ``weight(key, name, layer, dims)``
makes one leaf; the program's copy (``program_params``) calls the same
function, so the two agree without the reference ever reading what the
program holds.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import attention, mm, rmsnorm, rope

# fixed per-leaf stream ids: a leaf's values depend on the seed, its
# name and its layer, never on the order leaves are made in
LEAF_IDS = {"embed": 1, "unembed": 2, "ln_f": 3, "ln1": 4, "ln2": 5,
            "wq": 6, "wk": 7, "wv": 8, "wo": 9, "w_gate": 10, "w_up": 11,
            "w_down": 12}
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")

# the program's ModelCfg fields each published key of a configuration
# file sets
_MODEL_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv_heads",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    def trunk_flops(self) -> int:
        """Matmul flops of one token through every layer."""
        return 2 * matmul_params(self)

    def layer_contexts(self, ctx: int):
        """Every layer attends to the whole context."""
        return (ctx,) * self.n_layers


def model_dims(model: dict) -> Dims:
    """From a configuration file's ``model`` block (HF key names)."""
    return Dims(
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        d_ff=model["intermediate_size"],
        vocab=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]))


def matmul_params(dims: Dims) -> int:
    """Parameters of every layer's matmuls: q, k, v, o projections and
    the gated MLP."""
    d, H, KH, D, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                      dims.head_dim, dims.d_ff)
    return dims.n_layers * (d * (H + 2 * KH) * D + H * D * d + 3 * d * f)


def leaf_shape(name: str, dims: Dims):
    d, h, kh, hd, f, v = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                          dims.head_dim, dims.d_ff, dims.vocab)
    return {"embed": (v, d), "unembed": (d, v), "ln_f": (d,), "ln1": (d,),
            "ln2": (d,), "wq": (d, h, hd), "wk": (d, kh, hd),
            "wv": (d, kh, hd), "wo": (h, hd, d), "w_gate": (d, f),
            "w_up": (d, f), "w_down": (f, d)}[name]


def weight(key, name: str, layer, dims: Dims):
    """One float32 leaf.  Embedding and head are N(0, 0.02^2); RMSNorm
    weights 1 + N(0, 0.1^2), so a norm that drops its weight shows;
    projections uniform in +-1/sqrt(fan_in).  ``layer`` may be traced
    (the program's copy makes its stacked layers under ``vmap``)."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    shape = leaf_shape(name, dims)
    if name in ("embed", "unembed"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name.startswith("ln"):
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    fan_in = {"wo": dims.n_heads * dims.head_dim,
              "w_down": dims.d_ff}.get(name, dims.d_model)
    lim = fan_in ** -0.5
    return jax.random.uniform(k, shape, jnp.float32, -lim, lim)


def weights(key, dims: Dims) -> dict:
    """Every leaf, layers as a list (call under ``jax.jit``)."""
    top = {n: weight(key, n, 0, dims) for n in ("embed", "unembed", "ln_f")}
    top["layers"] = [{n: weight(key, n, i, dims) for n in LAYER_LEAVES}
                     for i in range(dims.n_layers)]
    return top


def hidden(w, tokens, dims: Dims, block: int, quant=None):
    """Final-normed hidden states [T, d] of one sequence."""
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens]
    for lw in w["layers"]:
        h = rmsnorm(x, lw["ln1"], dims.norm_eps)
        q = rope(mm("td,dhk->thk", h, lw["wq"], quant), pos, dims.rope_theta)
        k = rope(mm("td,dhk->thk", h, lw["wk"], quant), pos, dims.rope_theta)
        v = mm("td,dhk->thk", h, lw["wv"], quant)
        x = x + mm("thk,hkd->td", attention(q, k, v, block, quant),
                   lw["wo"], quant)
        h = rmsnorm(x, lw["ln2"], dims.norm_eps)
        g = jax.nn.silu(mm("td,df->tf", h, lw["w_gate"], quant))
        x = x + mm("tf,fd->td", g * mm("td,df->tf", h, lw["w_up"], quant),
                   lw["w_down"], quant)
    return rmsnorm(x, w["ln_f"], dims.norm_eps)


# ---------------------------------------------------------------------------
# the program's layout


def program_fields(model: dict) -> dict:
    """The program's ``ModelCfg`` fields the ``model`` block sets."""
    kw = {field: model[key] for key, field in _MODEL_FIELDS.items()}
    kw["head_dim"] = kw["d_model"] // kw["n_heads"]
    kw["tie_embeddings"] = bool(model["tie_word_embeddings"])
    return kw


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
