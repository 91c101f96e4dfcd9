"""Token-choice top-k Mixture-of-Experts with capacity-based dispatch.

Sharding strategy (EP over the 'model' mesh axis):
  * routing, dispatch-index construction and combine are LOCAL per batch row
    (vmapped scatters/gathers on [E, C]-shaped per-row tensors), so the SPMD
    partitioner never sees a cross-shard scatter;
  * the three expert einsums contract over stacked expert weights
    [E, d, f] sharded on E -> each model shard computes only its local
    experts; the dispatched activations are batch-sharded and E-replicated
    (bounded by the microbatch size, which gradient accumulation keeps small);
  * the expert outputs are re-replicated over E (one all-gather over the
    'model' axis per layer) before the local combine-gather - this is the EP
    collective, analogous to the second all-to-all of a classic MoE.

Capacity C = ceil(S * top_k / E * capacity_factor); overflow tokens are
dropped (their combine weight is 0), underflow slots read a zero row.
That capacity path is the training step's (``moe_ffn``, and
``moe_ffn_sharded`` under ``moe_impl="shard"``).

Serving (every mode but ``train``) runs ``expert_layer``: dropless over
every token of the launch, and told which experts it holds
(``MoECfg.expert_shards`` / ``expert_shard``).  It routes over all the
experts, keeps the (token, expert) pairs of its own, sorts them by
expert into a buffer of static size ``T * top_k`` with dynamic group
sizes, runs the grouped matmul (``kernels.ops.grouped_matmul``) over the
held experts only, and combines with the gates: one chip's share of an
expert-parallel layer, with no stand-in for the other chips' part.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.basic import act_fn
from repro.sharding import ctx


def capacity(seq, n_experts, top_k, factor):
    c = int(seq * top_k / n_experts * factor + 0.5)
    return max(8, ((c + 7) // 8) * 8)   # pad to a lane-friendly multiple


def init_moe(key, cfg):
    d, m = cfg.d_model, cfg.moe
    f = m.d_ff_expert
    k = jax.random.split(key, 5)
    lim_d, lim_f = d ** -0.5, f ** -0.5
    u = lambda kk, shape, l: jax.random.uniform(kk, shape, jnp.float32, -l, l)
    p = {
        "router": u(k[0], (d, m.n_experts), lim_d),
        "w_gate": u(k[1], (m.n_held, d, f), lim_d),
        "w_up": u(k[2], (m.n_held, d, f), lim_d),
        "w_down": u(k[3], (m.n_held, f, d), lim_f),
    }
    if m.n_shared:
        from repro.models.layers.basic import init_mlp
        p["shared"] = init_mlp(k[4], d, f * m.n_shared, gated=True)
    return p


def moe_specs(cfg):
    s = {
        "router": P("data", None),
        "w_gate": P("model", "data", None),
        "w_up": P("model", "data", None),
        "w_down": P("model", None, "data"),
    }
    if cfg.moe.n_shared:
        from repro.models.layers.basic import mlp_specs
        s["shared"] = mlp_specs(gated=True)
    return s


def top_k_gates(logits, top_k, normalize=True):
    """logits [..., E] f32 -> (gates [..., k], eid [..., k]): the softmax
    over all experts, its ``top_k`` largest, and with ``normalize``
    (``MoECfg.norm_topk_prob``) those divided by their sum."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eid = jax.lax.top_k(probs, top_k)
    if normalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eid


def _route(logits, top_k, cap, normalize=True):
    """logits [S,E] f32 -> (gates [S,k], eid [S,k], slot_pos [S,k], keep [S,k]).

    slot_pos is each (token, k)-slot's position within its expert's capacity
    buffer, assigned in token order (earlier tokens win on overflow).
    """
    S, E = logits.shape
    gates, eid = top_k_gates(logits, top_k, normalize)          # [S,k]
    oh = jax.nn.one_hot(eid.reshape(S * top_k), E, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1                            # [S*k,E]
    slot_pos = jnp.take_along_axis(
        pos, eid.reshape(S * top_k)[:, None], axis=1)[:, 0]
    keep = slot_pos < cap
    return gates, eid, slot_pos.reshape(S, top_k), keep.reshape(S, top_k)


def moe_ffn(p, x, cfg, batch_axes=("data",)):
    """x [B,S,D] -> (out [B,S,D], aux_losses dict)."""
    m = cfg.moe
    if m.n_held != m.n_experts:
        raise NotImplementedError("the capacity path holds every expert")
    cdt = x.dtype
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    C = capacity(S, E, K, m.capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cdt)
                        ).astype(jnp.float32)

    def route_row(lg):  # [S,E]
        gates, eid, slot_pos, keep = _route(lg, K, C, m.norm_topk_prob)
        tok = jnp.arange(S, dtype=jnp.int32)[:, None] * jnp.ones((1, K), jnp.int32)
        # dispatch index [E,C]: source token for each capacity slot (S = pad)
        e_flat = jnp.where(keep, eid, E).reshape(-1)            # drop -> OOB
        disp = jnp.full((E, C), S, jnp.int32).at[
            e_flat, slot_pos.reshape(-1)].set(tok.reshape(-1), mode="drop")
        # combine index [S,K] into flattened [E*C] (+pad row at E*C)
        comb = jnp.where(keep, eid * C + slot_pos, E * C)
        return disp, comb, gates

    disp_idx, comb_idx, gates = jax.vmap(route_row)(logits)

    # ---- dispatch (local gather; zero row padded at index S) ----------------
    xp = jnp.concatenate([x, jnp.zeros((B, 1, D), cdt)], axis=1)
    xe = jnp.take_along_axis(xp[:, :, None, :],
                             disp_idx.reshape(B, E * C)[:, :, None, None],
                             axis=1).reshape(B, E, C, D)
    xe = ctx.constrain(xe, "batch", None, None, None)

    # ---- expert FFN (einsums sharded on E over 'model') ---------------------
    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(cdt))
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(cdt))
    g = ctx.constrain(g, "batch", "model", None, None)
    h = act_fn(cfg.act)(g) * u
    y = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(cdt))
    # EP collective: re-replicate expert outputs across the model axis
    y = ctx.constrain(y, "batch", None, None, None)

    # ---- combine (local gather + weighted sum over k slots) -----------------
    yf = jnp.concatenate([y.reshape(B, E * C, D), jnp.zeros((B, 1, D), cdt)],
                         axis=1)
    ys = jnp.take_along_axis(yf[:, :, None, :],
                             comb_idx.reshape(B, S * K)[:, :, None, None],
                             axis=1).reshape(B, S, K, D)
    out = jnp.einsum("bskd,bsk->bsd", ys, gates.astype(cdt))
    out = ctx.constrain(out, "batch", None, None)

    if m.n_shared:
        from repro.models.layers.basic import mlp
        out = out + mlp(p["shared"], x, cfg.act)

    # ---- aux losses ----------------------------------------------------------
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(jnp.argmax(probs, -1), E)).reshape(-1, E), axis=0)
    frac_probs = jnp.mean(probs.reshape(-1, E), axis=0)
    aux = {
        "moe_load_balance": E * jnp.sum(frac_tokens * frac_probs),
        "moe_router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return out, aux


# ---------------------------------------------------------------------------
# the serving layer: dropless, over the experts this chip holds

# what ``expert_layer`` counts per launch; ``EXPERT_MAX`` merges over
# layers by maximum, the others by sum
EXPERT_PAIRS, EXPERT_MAX = "expert_pairs", "expert_max_load"


def expert_layer(p, x, cfg, valid=None):
    """x [B,S,D] -> (out [B,S,D], counts): this chip's part of the MoE
    layer for every token of the launch, none dropped.

    ``valid`` [B,S] bool marks real tokens (a masked decode row or a
    chunk's left padding is not); others route nowhere.  ``counts``:
    ``EXPERT_PAIRS``, the (token, held expert) pairs computed, and
    ``EXPERT_MAX``, the most pairs one held expert received (int32)."""
    from repro.kernels import ops as kops
    m = cfg.moe
    cdt = x.dtype
    B, S, D = x.shape
    T, K, Eh = B * S, m.top_k, m.n_held
    xt = x.reshape(T, D)
    # the router in float32: a top-k pick must not turn on bf16 rounding
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    gates, eid = top_k_gates(logits, K, m.norm_topk_prob)      # [T,K]
    local = eid - m.first_held
    held = (local >= 0) & (local < Eh)
    if valid is not None:
        held &= valid.reshape(T, 1)
    # pairs sorted by held expert into a [T*K] buffer; the rest go last,
    # past every group, where the grouped matmul writes nothing used
    key = jnp.where(held, local, Eh).reshape(T * K)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    xs = xt[order // K]                                         # [T*K, D]
    g = kops.grouped_matmul(xs, p["w_gate"].astype(cdt), sizes)
    u = kops.grouped_matmul(xs, p["w_up"].astype(cdt), sizes)
    h = (act_fn(cfg.act)(g) * u).astype(cdt)
    y = kops.grouped_matmul(h, p["w_down"].astype(cdt), sizes)
    # back to token order (a gather by the inverse permutation), then the
    # gate-weighted sum over each token's held picks
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=order.dtype))
    yk = jnp.where(held[..., None], y[inv].reshape(T, K, D), 0.0)
    out = jnp.einsum("tkd,tk->td", yk, jnp.where(held, gates, 0.0))
    out = out.astype(cdt).reshape(B, S, D)
    if m.n_shared:
        # every chip runs the shared expert on its own tokens alike
        from repro.models.layers.basic import mlp
        out = out + mlp(p["shared"], x, cfg.act)
    counts = {EXPERT_PAIRS: sizes.sum(), EXPERT_MAX: sizes.max()}
    return out, counts


# ---------------------------------------------------------------------------
# EP-sharded implementation (beyond-paper optimization, cfg.moe_impl="shard")
#
# Activations are replicated over the 'model' (EP) axis, so no expert-output
# all-gather is needed at all: each model shard dispatches the SAME routing
# decisions but keeps only the slots of its local experts, runs its local
# expert FFNs (FSDP weight shards explicitly cast to bf16 BEFORE the manual
# all-gather - half the wire of the auto-partitioned f32 gather), combines
# locally, and one bf16 psum of the partial outputs finishes the layer.
# Numerics are IDENTICAL to moe_ffn (same capacity competition per shard).


def moe_ffn_sharded(p, x, cfg):
    """x [B,S,D] (batch-sharded, model-replicated) -> (out, aux)."""
    from repro.sharding.ctx import _mapping
    mesh = jax.sharding.get_abstract_mesh()
    names = set(getattr(mesh, "axis_names", ()) or ())
    mapping = _mapping()
    model_ax = mapping["model"]
    if model_ax not in names or mesh.shape[model_ax] <= 1 \
            or cfg.moe.n_experts % mesh.shape[model_ax] != 0:
        return moe_ffn(p, x, cfg)
    batch_ax = tuple(a for a in mapping["batch"] if a in names)

    m = cfg.moe
    cdt = x.dtype
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    C = capacity(S, E, K, m.capacity_factor)
    n_sh = mesh.shape[model_ax]
    E_loc = E // n_sh

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cdt)
                        ).astype(jnp.float32)

    bspec = P(batch_ax if batch_ax else None, None, None)
    has_data = "data" in names and mesh.shape["data"] > 1
    wspec_in = P(model_ax, "data" if has_data else None, None)
    wspec_out = P(model_ax, None, "data" if has_data else None)

    def body(xb, lg, wg, wu, wd):
        shard = jax.lax.axis_index(model_ax)
        # FSDP gather of the local experts' weights, explicitly in bf16.
        # optimization_barrier pins the f32->bf16 convert BEFORE the gather:
        # without it XLA:CPU folds the convert into its f32-legalized dots
        # and the gather silently goes back to full f32 width.
        def cast(w):
            return jax.lax.optimization_barrier(w.astype(cdt))
        if has_data:
            wg = jax.lax.all_gather(cast(wg), "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(cast(wu), "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(cast(wd), "data", axis=2, tiled=True)
        else:
            wg, wu, wd = cast(wg), cast(wu), cast(wd)

        def route_row(lgr):                              # [S,E]
            gates, eid, slot_pos, keep = _route(lgr, K, C,
                                                m.norm_topk_prob)
            local = (eid >= shard * E_loc) & (eid < (shard + 1) * E_loc)
            keep = keep & local
            e_loc = eid - shard * E_loc
            tok = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[:, None], (S, K))
            e_flat = jnp.where(keep, e_loc, E_loc).reshape(-1)
            disp = jnp.full((E_loc, C), S, jnp.int32).at[
                e_flat, slot_pos.reshape(-1)].set(tok.reshape(-1),
                                                  mode="drop")
            comb = jnp.where(keep, e_loc * C + slot_pos, E_loc * C)
            return disp, comb, gates

        disp_idx, comb_idx, gates = jax.vmap(route_row)(lg)
        Bl = xb.shape[0]
        xp = jnp.concatenate([xb, jnp.zeros((Bl, 1, D), cdt)], axis=1)
        xe = jnp.take_along_axis(
            xp[:, :, None, :],
            disp_idx.reshape(Bl, E_loc * C)[:, :, None, None],
            axis=1).reshape(Bl, E_loc, C, D)
        g = jnp.einsum("becd,edf->becf", xe, wg)
        u = jnp.einsum("becd,edf->becf", xe, wu)
        y = jnp.einsum("becf,efd->becd", act_fn(cfg.act)(g) * u, wd)
        yf = jnp.concatenate([y.reshape(Bl, E_loc * C, D),
                              jnp.zeros((Bl, 1, D), cdt)], axis=1)
        ys = jnp.take_along_axis(
            yf[:, :, None, :],
            comb_idx.reshape(Bl, S * K)[:, :, None, None],
            axis=1).reshape(Bl, S, K, D)
        partial = jnp.einsum("bskd,bsk->bsd", ys, gates.astype(cdt))
        return jax.lax.psum(partial, model_ax)           # one bf16 psum

    out = jax.shard_map(
        body,
        in_specs=(bspec, bspec, wspec_in, wspec_in, wspec_out),
        out_specs=bspec,
    )(x, logits, p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared:
        from repro.models.layers.basic import mlp
        out = out + mlp(p["shared"], x, cfg.act)
    return out, _aux_losses(logits)


def _aux_losses(logits):
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(jnp.argmax(probs, -1), E)).reshape(-1, E), axis=0)
    frac_probs = jnp.mean(probs.reshape(-1, E), axis=0)
    return {
        "moe_load_balance": E * jnp.sum(frac_tokens * frac_probs),
        "moe_router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
