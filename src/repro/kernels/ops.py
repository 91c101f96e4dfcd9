"""Jit'd public wrappers for all Pallas kernels, plus the tuned-config
dispatch path.

``interpret`` defaults to True off-TPU so the same call sites work in CPU
tests (interpret mode executes the kernel body in Python — correctness, not
speed) and compile to Mosaic on real TPUs.

Launch configuration resolves in precedence order: explicit kwarg >
``config=`` mapping > autotuner cache lookup (``tuned=True`` consults the
installed ``repro.core.autotune`` handle) > the MXU-aligned default.  The
resolution happens OUTSIDE jit (each wrapper is a plain function over a
jitted inner), so tuned values become ordinary static arguments and the
lookup costs one dict probe per call."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the single source of launch-config defaults and divisor clamping
# (space.py is jax-free, so this does not drag accelerators into
# analytic paths)
from repro.core.autotune.space import TUNABLES, divisor_clamp
from repro.kernels import (flash_attention as _fa, microbench_alu as _alu,
                           microbench_chase as _chase, mxu_probe as _mxu,
                           paged_attention as _pa, ssm_scan as _ssm,
                           wkv6 as _wkv)
from repro.sharding import ctx

# kernel name -> default launch config (the pre-autotuner hardcoded values)
KERNEL_DEFAULTS = {name: dict(t.default_config)
                   for name, t in TUNABLES.items()}


def _default_interpret():
    return jax.default_backend() != "tpu"


def resolve_kernel_config(kernel, shapes, dtype, *, config=None, tuned=False,
                          explicit=None):
    """The dispatch-path resolver: explicit kwargs > ``config`` mapping >
    installed-autotuner cache hit > defaults.  Returns a complete plain
    dict of launch parameters for ``kernel``."""
    out = dict(KERNEL_DEFAULTS[kernel])
    if config is None and tuned:
        from repro.core.autotune import tuned_config
        config = tuned_config(kernel, shapes, str(jnp.dtype(dtype).name))
    if config:
        out.update({k: config[k] for k in out if k in config})
    if explicit:
        out.update({k: v for k, v in explicit.items() if v is not None})
    return out


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "block_q", "block_k",
                                             "acc_dtype", "interpret"))
def _fa_jit(q, k, v, causal, window, softcap, scale, block_q, block_k,
            acc_dtype, interpret):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, block_q=block_q,
                               block_k=block_k, acc_dtype=acc_dtype,
                               interpret=interpret)


def flash_attention(q, k, v, causal=True, window=None, softcap=None,
                    scale=None, block_q=None, block_k=None, acc_dtype=None,
                    config=None, tuned=False, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shapes = {"batch": q.shape[0], "seq_q": q.shape[1],
              "seq_kv": k.shape[1], "heads": q.shape[2],
              "kv_heads": k.shape[2], "head_dim": q.shape[3]}
    c = resolve_kernel_config(
        "flash_attention", shapes, q.dtype, config=config, tuned=tuned,
        explicit={"block_q": block_q, "block_k": block_k,
                  "acc_dtype": acc_dtype})
    return _fa_jit(q, k, v, causal, window, softcap, scale,
                   int(c["block_q"]), int(c["block_k"]),
                   str(c["acc_dtype"]), interpret)


@functools.partial(jax.jit, static_argnames=("scale", "window", "softcap",
                                             "interpret", "hbm",
                                             "num_splits", "mesh"))
def _pa_jit(q, k_pages, v_pages, block_tables, context_lens, scale, window,
            softcap, interpret, hbm, num_splits, mesh):
    if mesh is not None:
        # a sharded replica's step: GSPMD cannot partition the kernel, so
        # it runs per shard on the local heads and batch rows
        return _pa.paged_attention_sharded(
            q, k_pages, v_pages, block_tables, context_lens, mesh,
            scale=scale, window=window, softcap=softcap,
            num_splits=num_splits, hbm=hbm, interpret=interpret)
    fn = _pa.paged_attention_hbm if hbm else _pa.paged_attention
    return fn(q, k_pages, v_pages, block_tables, context_lens, scale=scale,
              window=window, softcap=softcap, num_splits=num_splits,
              interpret=interpret)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, window=None, softcap=None, num_splits=None,
                    config=None, tuned=False, interpret=None, hbm=None):
    """Paged decode attention.  Two tunable axes resolve differently:
    ``block_size`` is a CACHE-LAYOUT parameter, fixed here by
    ``k_pages.shape[1]`` — the paged serving engine consults the tuning
    cache (``Autotuner.config_for('paged_attention', ...)``) when it lays
    out the block pool, not at dispatch time.  ``num_splits`` (the
    split-KV flash-decoding grid axis) is a pure LAUNCH parameter and
    resolves right here, in the standard precedence order (explicit
    kwarg > ``config=`` > ``tuned=True`` cache hit > default), clamped
    to the table width so every split covers >= 0 whole pages.

    ``hbm`` selects the HBM-resident lowering (the pool stays in ``ANY``
    memory space; pages are double-buffered into VMEM per iteration) —
    the default on real TPUs, where staging a serving-sized pool into
    VMEM cannot fly.  Off-TPU the staged lowering stays the default
    (interpret-mode DMA is slower); pass ``hbm=True`` to exercise the
    production path under interpret mode (what CPU CI does).

    Inside ``sharding.ctx.use_kernel_mesh`` (a sharded replica's step)
    the kernel runs per shard through ``paged_attention_sharded``."""
    interpret = _default_interpret() if interpret is None else interpret
    if hbm is None:
        hbm = jax.default_backend() == "tpu"
    NB = block_tables.shape[1]
    shapes = {"batch": q.shape[0], "heads": q.shape[1],
              "kv_heads": k_pages.shape[2], "head_dim": q.shape[2],
              "ctx": NB * k_pages.shape[1]}
    c = resolve_kernel_config("paged_attention", shapes, q.dtype,
                              config=config, tuned=tuned,
                              explicit={"num_splits": num_splits})
    splits = max(min(int(c.get("num_splits", 1)), NB), 1)
    return _pa_jit(q, k_pages, v_pages, block_tables, context_lens, scale,
                   window, softcap, interpret, bool(hbm), splits,
                   ctx.kernel_mesh())


@jax.jit
def _gmm_jit(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)


def grouped_matmul(x, w, group_sizes):
    """Grouped matmul of the expert layer: ``x`` [M, K] holds rows sorted
    by group, ``w`` [G, K, N] one matrix per group, ``group_sizes`` [G]
    int32 the rows of each group; row ``i`` of group ``g`` gives
    ``x[i] @ w[g]`` (float32).  Rows past ``sum(group_sizes)`` belong to
    no group, and their values are unspecified.  ``jax.lax.ragged_dot``:
    on a TPU, XLA lowers it to its grouped-matmul kernels, whose device
    ops are named ``%ragged-dot-*`` (the compiler's names, not this
    wrapper's)."""
    return _gmm_jit(x, w, group_sizes)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def _ssm_jit(x, dt, B, C, A, block_d, interpret):
    return _ssm.ssm_scan(x, dt, B, C, A, block_d=block_d,
                         interpret=interpret)


def ssm_scan(x, dt, B, C, A, block_d=None, config=None, tuned=False,
             interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shapes = {"batch": x.shape[0], "seq": x.shape[1],
              "d_inner": x.shape[2], "state_dim": A.shape[1]}
    c = resolve_kernel_config("ssm_scan", shapes, x.dtype, config=config,
                              tuned=tuned, explicit={"block_d": block_d})
    return _ssm_jit(x, dt, B, C, A, int(c["block_d"]), interpret)


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def _wkv_jit(r, k, v, w, u, block_h, interpret):
    return _wkv.wkv6(r, k, v, w, u, block_h=block_h, interpret=interpret)


def wkv6(r, k, v, w, u, block_h=None, config=None, tuned=False,
         interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shapes = {"batch": r.shape[0], "seq": r.shape[1],
              "heads": r.shape[2], "head_dim": r.shape[3]}
    c = resolve_kernel_config("wkv6", shapes, r.dtype, config=config,
                              tuned=tuned, explicit={"block_h": block_h})
    return _wkv_jit(r, k, v, w, u, int(c["block_h"]), interpret)


@functools.partial(jax.jit, static_argnames=("op", "length", "dependent",
                                             "interpret"))
def alu_chain(x, c, op="fma", length=64, dependent=True, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _alu.alu_chain(x, c, op=op, length=length, dependent=dependent,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("hops", "interpret"))
def pointer_chase(nxt, start, hops=1024, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _chase.pointer_chase(nxt, start, hops=hops, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chain", "block", "interpret"))
def _mxu_jit(a, b, chain, block, interpret):
    return _mxu.mxu_probe(a, b, chain=chain, block=block,
                          interpret=interpret)


def mxu_probe(a, b, chain=4, block=None, config=None, tuned=False,
              interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shapes = {"m": a.shape[0], "k": a.shape[1], "n": b.shape[1]}
    explicit = None
    if block is not None:
        explicit = {"block_m": block[0], "block_n": block[1]}
    c = resolve_kernel_config("mxu_probe", shapes, a.dtype, config=config,
                              tuned=tuned, explicit=explicit)
    bm, bn = int(c["block_m"]), int(c["block_n"])
    if block is None:
        # config/cache-resolved blocks are perf hints (a bucketed cache
        # entry may not divide this exact problem): clamp to a divisor.
        # An EXPLICIT block= stays strict in the kernel — for measurement
        # callers the tile is the measured quantity itself.
        bm = divisor_clamp(bm, shapes["m"])
        bn = divisor_clamp(bn, shapes["n"])
    return _mxu_jit(a, b, chain, (bm, bn), interpret)
