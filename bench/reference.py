"""The plain reference: a llama-style dense GQA decoder in float32, and
the weights that both it and the program under test are given.

It follows the published description of InternLM2 and Yi (RMSNorm with
a weight, rotary embeddings on the split halves of each head, grouped
query attention, a SwiGLU MLP, untied embedding and head) and imports
nothing of the program: it runs one whole sequence at a time, with no
cache, no paging and no batching, every matmul at ``Precision.HIGHEST``.

Weights come from the seed alone.  ``weight(key, name, layer, dims)``
makes one leaf; the program's copy (``bench.program.program_params``)
calls the same function, so the two agree without the reference ever
reading what the program holds.

The control (``quant="fp8"``) is the same forward with every matmul's
two inputs rounded to float8 e4m3 (per-tensor scale, saturating at 448)
and accumulated in float32: the precision step below the bfloat16 that
the configurations state.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# fixed per-leaf stream ids: a leaf's values depend on the seed, its
# name and its layer, never on the order leaves are made in
LEAF_IDS = {"embed": 1, "unembed": 2, "ln_f": 3, "ln1": 4, "ln2": 5,
            "wq": 6, "wk": 7, "wv": 8, "wo": 9, "w_gate": 10, "w_up": 11,
            "w_down": 12}
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")


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

    @classmethod
    def from_model(cls, model: dict) -> "Dims":
        """From a configuration file's ``model`` block (HF key names)."""
        return cls(
            n_layers=model["num_hidden_layers"],
            d_model=model["hidden_size"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["hidden_size"] // model["num_attention_heads"],
            d_ff=model["intermediate_size"],
            vocab=model["vocab_size"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]))


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


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


# ---------------------------------------------------------------------------
# the forward pass


def fp8_e4m3(x):
    """Round to the nearest float8 e4m3 value, saturating at +-448
    (3 mantissa bits; exponents below -6 share the subnormal step)."""
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, 448.0)


def _q(x, quant):
    if quant is None:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return fp8_e4m3(x / s) * s


def mm(spec, a, b, quant=None):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, heads, hd]: rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # [T, hd/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, block: int, quant=None):
    """Causal GQA over one sequence.  q [T, H, hd]; k, v [T, KH, hd].
    Query blocks of ``block`` rows bound the score tensor."""
    T, H, hd = q.shape
    KH = k.shape[1]
    qg = q.reshape(T // block, block, KH, H // KH, hd)
    kpos = jnp.arange(T)

    def one(args):
        qb, start = args
        s = mm("qkgd,tkd->kgqt", qb, k, quant) * hd ** -0.5
        qpos = start + jnp.arange(block)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("kgqt,tkd->qkgd", p, v, quant)

    out = jax.lax.map(one, (qg, jnp.arange(T // block) * block))
    return out.reshape(T, H, hd)


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


@functools.partial(jax.jit, static_argnames=("dims", "block", "control"))
def served_gaps(w, tokens, at, served, dims: Dims, block: int,
                control: bool = False):
    """For one sequence ``tokens`` [T] (prompt, then served tokens, then
    padding): at each position ``at[j]`` the reference's best logit
    minus its logit of ``served[j]``, the token the program served
    next.  With ``control``, also the same gap of the token that the
    fp8 forward puts first there.  Returns ``(gaps, control_gaps)``."""
    x = hidden(w, tokens, dims, block)[at]
    logits = mm("nd,dv->nv", x, w["unembed"])
    best = jnp.max(logits, -1)
    gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    xc = hidden(w, tokens, dims, block, quant="fp8")[at]
    pick = jnp.argmax(mm("nd,dv->nv", xc, w["unembed"], "fp8"), -1)
    return gap, best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
