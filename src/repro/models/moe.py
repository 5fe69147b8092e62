"""Mixture-of-Experts FFN (Qwen-MoE family): routed top-k experts with an
optional always-on shared expert, plus a load-balance auxiliary loss.

Three dispatch implementations; the model runs `dense`, and only tests
select the others:
  * dense  — every expert processes every token, combine weights zero out
             non-selected experts. Exact, partitioner-trivial, O(E/topk)
             FLOPs overhead; also the oracle.
  * ragged — tokens sorted by expert, jax.lax.ragged_dot group matmuls;
             FLOPs proportional to activated experts only.
  * ep     — experts sharded over the 'model' axis under shard_map, tokens
             past a per-expert capacity dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(key, cfg):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    gated = layers.gated_activation(cfg.activation)
    p = {
        "router": layers.dense_init(ks[0], (d, m.num_experts)),
        "wi": layers.dense_init(ks[1], (m.num_experts, d, m.d_ff_expert)),
        "wo": layers.dense_init(ks[2], (m.num_experts, m.d_ff_expert, d),
                                in_axis_size=m.d_ff_expert),
    }
    if gated:
        p["wg"] = layers.dense_init(ks[3], (m.num_experts, d, m.d_ff_expert))
    if m.num_shared_experts:
        p["shared"] = {
            "wi": layers.dense_init(ks[4], (d, m.d_ff_shared)),
            "wo": layers.dense_init(ks[5], (m.d_ff_shared, d),
                                    in_axis_size=m.d_ff_shared),
        }
        if gated:
            p["shared"]["wg"] = layers.dense_init(ks[6], (d, m.d_ff_shared))
        p["shared_gate"] = layers.dense_init(ks[6], (d, 1))
    return p


def _routing(params, x, cfg):
    """x [T, D] -> (weights [T, k], idx [T, k], aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    density = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, m.num_experts), axis=1), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(density * density_proxy) * m.router_aux_coef
    return weights.astype(x.dtype), idx, aux


def _apply_dense(params, x, cfg, weights, idx):
    """Dense dispatch: combine [T, E] (zeros off top-k) einsum over experts."""
    m = cfg.moe
    combine = jnp.zeros((x.shape[0], m.num_experts), x.dtype)
    combine = jnp.sum(
        jax.nn.one_hot(idx, m.num_experts, dtype=x.dtype)
        * weights[..., None], axis=1)
    h = jnp.einsum("td,edf->tef", x, params["wi"].astype(x.dtype))
    if "wg" in params:
        g = jnp.einsum("td,edf->tef", x, params["wg"].astype(x.dtype))
        h = layers.act_fn(cfg.activation)(g) * h
    else:
        h = layers.act_fn(cfg.activation)(h)
    # weight the expert activations BEFORE the down-projection so the
    # [T, E, D] tensor is never materialized (it dominates memory at 32k)
    h = h * combine[:, :, None]
    return jnp.einsum("tef,efd->td", h, params["wo"].astype(x.dtype))


def _apply_ragged(params, x, cfg, weights, idx):
    """Sorted + ragged_dot dispatch: FLOPs ~ activated experts only."""
    m = cfg.moe
    t = x.shape[0]
    k = m.top_k
    # replicate each token k times, sort replica stream by expert id
    flat_expert = idx.reshape(-1)                       # [T*k]
    order = jnp.argsort(flat_expert)                    # stable
    inv_token = order // k                              # source token per slot
    xs = x[inv_token]                                   # [T*k, D] sorted by expert
    group_sizes = jnp.bincount(flat_expert, length=m.num_experts)

    h = jax.lax.ragged_dot(xs, params["wi"].astype(x.dtype), group_sizes)
    if "wg" in params:
        g = jax.lax.ragged_dot(xs, params["wg"].astype(x.dtype), group_sizes)
        h = layers.act_fn(cfg.activation)(g) * h
    else:
        h = layers.act_fn(cfg.activation)(h)
    y = jax.lax.ragged_dot(h, params["wo"].astype(x.dtype), group_sizes)

    w_sorted = weights.reshape(-1)[order][:, None].astype(y.dtype)
    y = y * w_sorted
    # scatter-add back to tokens
    out = jnp.zeros((t, x.shape[1]), y.dtype).at[inv_token].add(y)
    return out


def _apply_ep(params, x, cfg, weights, idx, capacity_factor: float = 2.0):
    """Expert-parallel dispatch under shard_map (beyond-paper optimization).

    Tokens stay on their data shard; experts are sharded over the TP
    ('model') axis. Each (data, model) device selects the (token, k) pairs
    routed to ITS local experts (<= capacity 2*T_loc*topk/EP), runs a
    LOCAL ragged_dot over them, scatter-adds back, and a single psum over
    'model' combines expert contributions — no all-to-all, no global sort,
    and compute proportional to activated experts instead of all of them.
    Semantically exact up to capacity overflow (2x slack; the router aux
    loss keeps loads balanced)."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as sh

    mesh = sh.current_mesh()
    m = cfg.moe
    e = m.num_experts
    k = m.top_k
    if mesh is None or "model" not in mesh.axis_names \
            or e % int(mesh.shape["model"]) != 0:
        return _apply_ragged(params, x, cfg, weights, idx)

    ep = int(mesh.shape["model"])
    e_loc = e // ep
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # weights are FSDP-sharded over 'data' only (pod-replicated)
    fsdp_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    gated = "wg" in params

    def local(x_loc, w_loc, i_loc, wi, wg, wo):
        # weights arrive FSDP-sharded on D; gather them (model-local slice)
        if fsdp_axes:
            wi = jax.lax.all_gather(wi, fsdp_axes, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp_axes, axis=2, tiled=True)
            if gated:
                wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
        t_loc = x_loc.shape[0]
        # per-expert token capacity (expected t_loc*k/e, with slack)
        cap_e = max(1, int(capacity_factor * t_loc * k / e))
        eid0 = jax.lax.axis_index("model") * e_loc
        flat_e = i_loc.reshape(-1)                       # [T_loc*k]
        local_e = flat_e - eid0
        hit = (local_e >= 0) & (local_e < e_loc)
        sort_key = jnp.where(hit, local_e, e_loc)        # misses last
        order = jnp.argsort(sort_key)                    # stable
        gs = jnp.bincount(jnp.clip(sort_key, 0, e_loc),
                          length=e_loc + 1)[:e_loc]      # hits per expert
        starts = jnp.cumsum(gs) - gs
        # capacity-padded [e_loc, cap_e] slot -> (token, k)-pair positions
        slot = jnp.arange(cap_e)
        pos = jnp.clip(starts[:, None] + slot[None, :], 0, t_loc * k - 1)
        rows = order[pos]                                # [e_loc, cap_e]
        valid = slot[None, :] < jnp.minimum(gs, cap_e)[:, None]
        toks = rows // k
        xs = x_loc[toks] * valid[..., None].astype(x_loc.dtype)
        # grouped einsums with static shapes (exact HLO flop accounting;
        # compute = e_loc*cap_e rows instead of dense's t_loc*e_loc)
        h = jnp.einsum("ecd,edf->ecf", xs, wi.astype(xs.dtype))
        if gated:
            g = jnp.einsum("ecd,edf->ecf", xs, wg.astype(xs.dtype))
            h = layers.act_fn(cfg.activation)(g) * h
        else:
            h = layers.act_fn(cfg.activation)(h)
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(xs.dtype))
        wsel = (w_loc.reshape(-1)[rows]
                * valid.astype(w_loc.dtype))             # [e_loc, cap_e]
        y = y * wsel[..., None].astype(y.dtype)
        out = jnp.zeros_like(x_loc).at[toks.reshape(-1)].add(
            y.reshape(-1, x_loc.shape[1]))
        return jax.lax.psum(out, "model")

    batch_spec = P(data_axes if len(data_axes) > 1 else
                   (data_axes[0] if data_axes else None))
    tok_spec = P(batch_spec[0], None)
    wi_spec = P("model", "data" if "data" in mesh.axis_names else None, None)
    wo_spec = P("model", None, "data" if "data" in mesh.axis_names else None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(batch_spec[0], None), P(batch_spec[0], None),
                  wi_spec, wi_spec, wo_spec),
        out_specs=tok_spec,
        check_vma=False)
    wg = params.get("wg", params["wi"])
    return fn(x, weights, idx, params["wi"], wg, params["wo"])


def apply_moe(params, x, cfg, impl: str = "dense", capacity: float = 2.0):
    """x [B, S, D] -> (y [B, S, D], aux_loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    weights, idx, aux = _routing(params, xt, cfg)
    if impl == "dense":
        y = _apply_dense(params, xt, cfg, weights, idx)
    elif impl == "ragged":
        y = _apply_ragged(params, xt, cfg, weights, idx)
    elif impl == "ep":
        y = _apply_ep(params, xt, cfg, weights, idx,
                      capacity_factor=capacity)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")
    if "shared" in params:
        sh = params["shared"]
        h = jnp.einsum("td,df->tf", xt, sh["wi"].astype(x.dtype))
        if "wg" in sh:
            g = jnp.einsum("td,df->tf", xt, sh["wg"].astype(x.dtype))
            h = layers.act_fn(cfg.activation)(g) * h
        else:
            h = layers.act_fn(cfg.activation)(h)
        ys = jnp.einsum("tf,fd->td", h, sh["wo"].astype(x.dtype))
        gate = jax.nn.sigmoid(
            jnp.einsum("td,de->te", xt.astype(jnp.float32),
                       params["shared_gate"].astype(jnp.float32)))
        y = y + ys * gate.astype(y.dtype)
    return y.reshape(b, s, d), aux
