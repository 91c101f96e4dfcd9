"""Activation-sharding context.

Model code calls ``constrain(x, *logical_axes)`` at anchor points (post-embed,
layer carries, attention heads, MLP hidden, logits).  The step builder sets
the mapping from logical axes to mesh axes for the current launch; with no
mesh in context the constraints are no-ops, so the same model code runs in
CPU smoke tests and in the 512-device dry-run.

Logical activation axes: 'batch', 'model' (TP/heads/ffn), 'seq' (SP/decode
KV), None (replicated).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _mapping():
    return getattr(_state, "mapping", {"batch": ("data",), "model": "model",
                                       "seq": "model"})


@contextlib.contextmanager
def use_axes(batch=("data",), model="model", seq="model"):
    old = getattr(_state, "mapping", None)
    _state.mapping = {"batch": tuple(batch), "model": model, "seq": seq}
    try:
        yield
    finally:
        if old is None:
            del _state.mapping
        else:
            _state.mapping = old


@contextlib.contextmanager
def use_kernel_mesh(mesh):
    """Trace the enclosed code for a replica spanning ``mesh``: kernels
    that GSPMD cannot partition (``kernels.ops.paged_attention``) read
    it with ``kernel_mesh()`` and run per shard.  Activation
    constraints stay as they are."""
    old = getattr(_state, "kernel_mesh", None)
    _state.kernel_mesh = mesh
    try:
        yield
    finally:
        _state.kernel_mesh = old


def kernel_mesh():
    """The mesh set by the innermost ``use_kernel_mesh``, else None."""
    return getattr(_state, "kernel_mesh", None)


def spec(*logical) -> P:
    m = _mapping()
    return P(*(m.get(a) if a is not None else None for a in logical))


def constrain(x, *logical):
    """with_sharding_constraint if a usable mesh is in context, else no-op.

    Axes whose dim is not divisible by the mesh-axis size are replicated
    instead (e.g. gemma3's single KV head over 16-way model parallelism).
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = set(mesh.axis_names)
    if not names:
        return x
    sizes = dict(mesh.shape)
    fixed = []
    used_any = False
    for i, a in enumerate(tuple(spec(*logical))):
        if a is None or i >= x.ndim:
            fixed.append(None)
            continue
        axes = (a,) if isinstance(a, str) else tuple(a)
        if not set(axes).issubset(names):
            fixed.append(None)
            continue
        total = 1
        for ax in axes:
            total *= sizes.get(ax, 1)
        if total > 1 and x.shape[i] % total == 0:
            fixed.append(a)
            used_any = True
        else:
            fixed.append(None)
    if not used_any:
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))
