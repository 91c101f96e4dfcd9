"""The plain reference of an OLMoE-style sparse-expert decoder in float32,
the weights that both it and the program under test are given, and the
program's layout of them.

It follows the published description of OLMoE (arXiv:2409.02060, and
``OlmoeForCausalLM`` of the model's own ``config.json``): RMSNorm with a
weight (eps 1e-5); multi-head attention whose projected ``q`` and ``k``
are each RMS-normed over their whole width (all heads at once) before
the split into heads and the rotary embedding; and in every layer a
router over all ``n_experts`` experts, a softmax, its ``top_k`` largest
kept as gates (divided by their sum only where ``norm_topk_prob`` says
so; OLMoE does not), and SwiGLU experts of width ``d_ff``; no shared
expert; untied embedding and head.  It imports nothing of the program:
it runs one whole sequence at a time, with no cache, no paging and no
batching, every matmul at ``Precision.HIGHEST`` through
``reference.mm``.

One chip's share: the configuration holds ``n_held`` experts of each
layer, ``first_held`` onwards, of an expert-parallel group over
``n_experts // n_held`` chips.  The router keeps all its outputs; the
layer adds what the held experts give, weighted by their gates, and
leaves out what the other chips' experts would add.  Every expert's
weights come from the seed and its global index, so the shares of one
layer add up to the uncut layer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import attention, mm, rmsnorm, rope

LEAF_IDS = {"embed": 1, "unembed": 2, "ln_f": 3, "ln1": 4, "ln2": 5,
            "wq": 6, "wk": 7, "wv": 8, "wo": 9, "q_norm": 10, "k_norm": 11,
            "router": 12, "w_gate": 13, "w_up": 14, "w_down": 15}
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "router")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                   # one expert's width
    vocab: int
    rope_theta: float
    norm_eps: float
    n_experts: int              # the router's outputs, every chip's experts
    top_k: int
    n_held: int                 # experts this chip holds in each layer
    first_held: int
    norm_topk_prob: bool

    def trunk_flops(self) -> int:
        """Matmul flops of one token through every layer: attention's
        projections, the router, and this chip's mean share of the
        expert work, ``top_k * n_held / n_experts`` experts."""
        d, H, KH, D, f = (self.d_model, self.n_heads, self.n_kv_heads,
                          self.head_dim, self.d_ff)
        attn = d * (H + 2 * KH) * D + H * D * d
        experts = 3 * d * f * self.top_k * self.n_held // self.n_experts
        return 2 * self.n_layers * (attn + d * self.n_experts + experts)

    def layer_contexts(self, ctx: int):
        """Every layer attends to the whole context."""
        return (ctx,) * self.n_layers


def model_dims(model: dict) -> Dims:
    """From a configuration file's ``model`` block: HF key names, with
    ``num_experts`` the experts held here and ``expert_shards`` /
    ``expert_shard`` the chip's place in the expert-parallel group."""
    held, shards = model["num_experts"], model["expert_shards"]
    return Dims(
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        d_ff=model["intermediate_size"],
        vocab=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        n_experts=held * shards,
        top_k=model["num_experts_per_tok"],
        n_held=held,
        first_held=held * model["expert_shard"],
        norm_topk_prob=bool(model["norm_topk_prob"]))


def leaf_shape(name: str, dims: Dims):
    d, h, kh, hd, f, v = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                          dims.head_dim, dims.d_ff, dims.vocab)
    return {"embed": (v, d), "unembed": (d, v), "ln_f": (d,), "ln1": (d,),
            "ln2": (d,), "wq": (d, h, hd), "wk": (d, kh, hd),
            "wv": (d, kh, hd), "wo": (h, hd, d), "q_norm": (h * hd,),
            "k_norm": (kh * hd,), "router": (d, dims.n_experts),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}[name]


def weight(key, name: str, layer, dims: Dims, expert=0):
    """One float32 leaf (of global expert ``expert`` for an expert's
    matrix).  Embedding and head N(0, 0.02^2); RMSNorm weights, QK-norm
    ones too, 1 + N(0, 0.1^2); projections, router and experts uniform
    in +-1/sqrt(fan_in).  ``layer`` and ``expert`` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, LEAF_IDS[name]), layer), expert)
    shape = leaf_shape(name, dims)
    if name in ("embed", "unembed"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name.startswith("ln") or name.endswith("_norm"):
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    fan_in = {"wo": dims.n_heads * dims.head_dim,
              "w_down": dims.d_ff}.get(name, dims.d_model)
    lim = fan_in ** -0.5
    return jax.random.uniform(k, shape, jnp.float32, -lim, lim)


def held_experts(key, name: str, layer, dims: Dims):
    """[n_held, ...]: the matrices of the experts this chip holds."""
    ids = dims.first_held + jnp.arange(dims.n_held)
    return jax.vmap(lambda e: weight(key, name, layer, dims, e))(ids)


def weights(key, dims: Dims) -> dict:
    """Every leaf, layers as a list (call under ``jax.jit``)."""
    top = {n: weight(key, n, 0, dims) for n in ("embed", "unembed", "ln_f")}
    top["layers"] = [
        {**{n: weight(key, n, i, dims) for n in LAYER_LEAVES},
         **{n: held_experts(key, n, i, dims) for n in EXPERT_LEAVES}}
        for i in range(dims.n_layers)]
    return top


def gate_weights(h, router, dims: Dims, quant=None):
    """[T, n_held]: each token's gate on each held expert, 0 where the
    expert is not among its ``top_k``."""
    probs = jax.nn.softmax(mm("td,de->te", h, router, quant), axis=-1)
    gates, eid = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk_prob:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    pick = jax.nn.one_hot(eid - dims.first_held, dims.n_held,
                          dtype=jnp.float32)          # out of range: 0
    return jnp.einsum("tk,tke->te", gates, pick)


def expert_mix(lw, h, dims: Dims, quant=None):
    """The held experts' part of the layer: every held expert on every
    token, weighted by the token's gate on it."""
    w = gate_weights(h, lw["router"], dims, quant)
    g = jax.nn.silu(mm("td,edf->tef", h, lw["w_gate"], quant))
    u = mm("td,edf->tef", h, lw["w_up"], quant)
    y = mm("tef,efd->ted", g * u, lw["w_down"], quant)
    return jnp.einsum("ted,te->td", y, w)


def hidden(w, tokens, dims: Dims, block: int, quant=None):
    """Final-normed hidden states [T, d] of one sequence."""
    T = tokens.shape[0]
    H, KH, D = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(T)
    x = w["embed"][tokens]
    for lw in w["layers"]:
        h = rmsnorm(x, lw["ln1"], dims.norm_eps)
        # QK-norm over the whole projection, then heads and rotation
        q = rmsnorm(mm("td,dhk->thk", h, lw["wq"], quant).reshape(T, H * D),
                    lw["q_norm"], dims.norm_eps).reshape(T, H, D)
        k = rmsnorm(mm("td,dhk->thk", h, lw["wk"], quant).reshape(T, KH * D),
                    lw["k_norm"], dims.norm_eps).reshape(T, KH, D)
        v = mm("td,dhk->thk", h, lw["wv"], quant)
        q, k = rope(q, pos, dims.rope_theta), rope(k, pos, dims.rope_theta)
        x = x + mm("thk,hkd->td", attention(q, k, v, block, quant),
                   lw["wo"], quant)
        x = x + expert_mix(lw, rmsnorm(x, lw["ln2"], dims.norm_eps), dims,
                           quant)
    return rmsnorm(x, w["ln_f"], dims.norm_eps)


# ---------------------------------------------------------------------------
# the program's layout


def program_fields(model: dict) -> dict:
    """The program's ``ModelCfg`` fields the ``model`` block sets.  The
    expert block is the program's own ``MoECfg``, made here; nothing of
    the program is used by the forward pass above."""
    from repro.configs.base import MoECfg
    dims = model_dims(model)
    return {
        "n_layers": dims.n_layers, "d_model": dims.d_model,
        "n_heads": dims.n_heads, "n_kv_heads": dims.n_kv_heads,
        "head_dim": dims.head_dim, "d_ff": dims.d_ff,
        "vocab_size": dims.vocab, "rope_theta": dims.rope_theta,
        "norm_eps": dims.norm_eps,
        "tie_embeddings": bool(model["tie_word_embeddings"]),
        "qk_norm": True, "qk_norm_width": "full",
        "moe": MoECfg(n_experts=dims.n_experts, top_k=dims.top_k,
                      d_ff_expert=dims.d_ff, n_shared=0,
                      norm_topk_prob=dims.norm_topk_prob,
                      expert_shards=dims.n_experts // dims.n_held,
                      expert_shard=dims.first_held // dims.n_held),
    }


def _norm_scale(w):
    """The program's RMSNorm computes ``x * (1 + scale)``."""
    return {"scale": w - 1.0}


def program_params(key, dims: Dims, cfg):
    """The reference's weights in the program's parameter layout (call
    under ``jax.jit``).  Stacked layers are made under ``vmap`` of the
    same functions the reference calls."""
    if cfg.padded_vocab != dims.vocab or cfg.padded_heads != dims.n_heads:
        raise ValueError("padded vocabulary or heads: not a layout this "
                         "benchmark maps")
    layers = jnp.arange(dims.n_layers)
    stack = {n: jax.vmap(lambda i, n=n: weight(key, n, i, dims))(layers)
             for n in LAYER_LEAVES}
    stack.update({n: jax.vmap(lambda i, n=n: held_experts(key, n, i, dims))(
        layers) for n in EXPERT_LEAVES})
    attn = {n: stack[n] for n in ("wq", "wk", "wv", "wo")}
    attn.update(q_norm=_norm_scale(stack["q_norm"]),
                k_norm=_norm_scale(stack["k_norm"]))
    return {
        "embed": {"table": weight(key, "embed", 0, dims),
                  "unembed": weight(key, "unembed", 0, dims)},
        "ln_f": _norm_scale(weight(key, "ln_f", 0, dims)),
        "pre_layers": [],
        "layers": {
            "ln1": _norm_scale(stack["ln1"]),
            "ln2": _norm_scale(stack["ln2"]),
            "attn": attn,
            "ffn": {n: stack[n] for n in ("router",) + EXPERT_LEAVES},
        },
    }
