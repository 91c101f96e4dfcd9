"""What every plain reference shares: the seeded key, the fp8 control's
rounding, ``mm`` (every matmul at ``Precision.HIGHEST``), RMSNorm with a
weight, rotary embeddings on the split halves of each head, causal
grouped-query attention over one whole sequence, and ``served_gaps``,
which compares the served tokens with a family's forward pass.  A
family's layers, weights and the program's layout of them are in its
own module, ``bench/references/<name>.py``, which a configuration file
names.  Nothing here imports the program.

The control (``quant="fp8"``) is the same forward with every matmul's
two inputs rounded to float8 e4m3 (per-tensor scale, saturating at 448)
and accumulated in float32: the precision step below the bfloat16 that
the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


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


@functools.partial(jax.jit,
                   static_argnames=("hidden", "dims", "block", "control"))
def served_gaps(w, tokens, at, served, hidden, dims, block: int,
                control: bool = False):
    """For one sequence ``tokens`` [T] (prompt, then served tokens, then
    padding): at each position ``at[j]`` the reference's best logit
    minus its logit of ``served[j]``, the token the program served
    next, ``hidden`` being the family's forward pass to the final norm.
    With ``control``, also the same gap of the token that the fp8
    forward puts first there.  Returns ``(gaps, control_gaps)``."""
    x = hidden(w, tokens, dims, block)[at]
    logits = mm("nd,dv->nv", x, w["unembed"])
    best = jnp.max(logits, -1)
    gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    xc = hidden(w, tokens, dims, block, quant="fp8")[at]
    pick = jnp.argmax(mm("nd,dv->nv", xc, w["unembed"], "fp8"), -1)
    return gap, best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
