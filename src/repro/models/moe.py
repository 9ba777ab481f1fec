"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Two execution paths, same math:

- **local** (mesh=None): single-device reference — sort-based dispatch with
  a global capacity. The oracle for tests and the smoke-test path.
- **expert-parallel shard_map** (mesh given): tokens stay sharded over the
  data axes; routing, top-k and capacity are computed *per shard* (the
  standard EP formulation); a pair of ``all_to_all`` collectives moves
  grouped tokens expert-shard-wise ([E, C_loc, d] -> [E_loc, P·C_loc, d])
  and back. Expert weights are sharded over the ``model`` axis on the expert
  dimension. This keeps HLO FLOPs ≈ active-param FLOPs × capacity_factor —
  a pure-GSPMD lowering of scatter/sort dispatch instead replicates the
  token stream per device (measured 20× useful FLOPs at 128 experts).

Dispatch itself is sort-based, not one-hot-einsum: a [T, E, C] dispatch
einsum costs T·E·C·d MACs — orders of magnitude more than the useful expert
compute at E=128. Router runs in fp32. A Switch-style aux load-balance loss
is returned for training.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import activation, dense_init


def init_moe(key, cfg: ModelConfig):
    keys = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": dense_init(keys[0], (d, e)),
        "wi_gate": dense_init(keys[1], (e, d, f)),
        "wi_up": dense_init(keys[2], (e, d, f)),
        "wo": dense_init(keys[3], (e, f, d)),
    }


def _route_and_group(xt, router, cfg: ModelConfig, capacity: int):
    """Shared routing + sort-based grouping. xt: [T, d].

    Returns (grouped [E, C, d], dest [T*k], st [T*k], sw [T*k], aux scalar).
    dest == E*C marks dropped slots.
    """
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(density * jnp.mean(probs, axis=0))

    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_w = top_p.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    grp_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos_in_e = jnp.arange(t * k) - grp_start[se]
    keep = pos_in_e < capacity
    dest = jnp.where(keep, se * capacity + pos_in_e, e * capacity)

    xg = xt[st]
    buf = jnp.zeros((e * capacity + 1, d), xt.dtype).at[dest].set(xg)
    grouped = buf[: e * capacity].reshape(e, capacity, d)
    return grouped, dest, st, sw, aux


def _expert_ffn(grouped, wg, wu, wo, act_name: str):
    """grouped: [E?, C, d] x per-expert weights [E?, d, f] -> [E?, C, d]."""
    act = activation(act_name)
    h = act(jnp.einsum("ecd,edf->ecf", grouped, wg)) \
        * jnp.einsum("ecd,edf->ecf", grouped, wu)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _ungroup(out_g, dest, st, sw, t: int, d: int):
    """Scatter expert outputs back to tokens, gate-weighted."""
    e_cap = out_g.shape[0] * out_g.shape[1]
    out_flat = jnp.concatenate(
        [out_g.reshape(e_cap, d), jnp.zeros((1, d), out_g.dtype)], axis=0)
    per_slot = out_flat[dest] * sw[:, None].astype(out_g.dtype)
    return jnp.zeros((t, d), jnp.float32).at[st].add(
        per_slot.astype(jnp.float32))


def apply_moe(params, x, cfg: ModelConfig, *, mesh: Any = None,
              dp_axes: Tuple = ("data",), ep_axis: Optional[str] = "model"):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar).

    ep_axis=None with a mesh -> pure data-parallel shard_map: experts
    replicated, routing/dispatch fully shard-local, zero collectives — the
    population-style layout for on-device-scale MoEs (§Perf pair 3).
    """
    b, s, d = x.shape
    compute_dtype = jnp.dtype(cfg.dtype)
    e, k = cfg.n_experts, cfg.top_k

    if mesh is not None and ep_axis is None:
        from jax.sharding import PartitionSpec as P

        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        b_shard = dp_axes if b % dp_size == 0 else None
        t_loc = (b // dp_size if b_shard else b) * s
        capacity = int(max(1, round(t_loc * k / e * cfg.capacity_factor)))

        def dp_moe(router, wg, wu, wo, xs):
            bl = xs.shape[0]
            xt = xs.reshape(bl * s, d).astype(compute_dtype)
            grouped, dest, st, sw, aux = _route_and_group(xt, router, cfg,
                                                          capacity)
            out_g = _expert_ffn(grouped, wg.astype(compute_dtype),
                                wu.astype(compute_dtype),
                                wo.astype(compute_dtype), cfg.act)
            out = _ungroup(out_g, dest, st, sw, bl * s, d)
            return (out.reshape(bl, s, d).astype(xs.dtype),
                    jax.lax.pmean(aux, dp_axes))

        fn = jax.shard_map(dp_moe, mesh=mesh,
                           in_specs=(P(), P(), P(), P(), P(b_shard)),
                           out_specs=(P(b_shard), P()), check_vma=False)
        return fn(params["router"], params["wi_gate"], params["wi_up"],
                  params["wo"], x)

    if mesh is None:
        t = b * s
        capacity = int(max(1, round(t * k / e * cfg.capacity_factor)))
        xt = x.reshape(t, d).astype(compute_dtype)
        grouped, dest, st, sw, aux = _route_and_group(
            xt, params["router"], cfg, capacity)
        out_g = _expert_ffn(grouped, params["wi_gate"].astype(compute_dtype),
                            params["wi_up"].astype(compute_dtype),
                            params["wo"].astype(compute_dtype), cfg.act)
        out = _ungroup(out_g, dest, st, sw, t, d)
        return out.reshape(b, s, d).astype(x.dtype), aux

    # ---- expert-parallel shard_map path -----------------------------------
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[ep_axis]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    assert e % ep == 0, (e, ep)
    b_shard = dp_axes if b % dp_size == 0 else None
    t_loc = (b // dp_size if b_shard else b) * s
    # activations arrive replicated over the model axis (TP layout); each
    # expert-parallel peer takes a distinct 1/ep slice of the local tokens
    # (sequence-parallel split), so EP compute and bandwidth scale with ep.
    t_ep = max(t_loc // ep, 1)
    capacity = int(max(1, round(t_ep * k / e * cfg.capacity_factor)))

    def local_moe(router, wg, wu, wo, xs):
        # xs: [B_loc, S, d] tokens local to this data shard (replicated on ep)
        bl = xs.shape[0]
        xt = xs.reshape(bl * s, d).astype(compute_dtype)
        idx = jax.lax.axis_index(ep_axis)
        if t_loc >= ep:
            xt = jax.lax.dynamic_slice_in_dim(xt, idx * t_ep, t_ep, axis=0)
        grouped, dest, st, sw, aux = _route_and_group(xt, router, cfg, capacity)
        # [E, C, d] -> [E/ep, ep*C, d]: exchange groups with expert shards.
        # split_axis == concat_axis (device-major swap) keeps the a2a VJP
        # well-formed; layout bookkeeping is done with transposes.
        g4 = grouped.reshape(ep, e // ep, capacity, d)
        g4 = jax.lax.all_to_all(g4, ep_axis, split_axis=0, concat_axis=0,
                                tiled=False)   # [peer, E/ep, C, d]
        g4 = jnp.moveaxis(g4, 0, 1)            # [E/ep, peer, C, d]
        out_g = _expert_ffn(g4.reshape(e // ep, ep * capacity, d),
                            wg.astype(compute_dtype), wu.astype(compute_dtype),
                            wo.astype(compute_dtype), cfg.act)
        o4 = jnp.moveaxis(out_g.reshape(e // ep, ep, capacity, d), 1, 0)
        o4 = jax.lax.all_to_all(o4, ep_axis, split_axis=0, concat_axis=0,
                                tiled=False)   # [expert-owner, E/ep, C, d]
        out = _ungroup(o4.reshape(e, capacity, d), dest, st, sw, xt.shape[0], d)
        if t_loc >= ep:
            out = jax.lax.all_gather(out, ep_axis, axis=0, tiled=True)
            out = out[: bl * s]
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep_axis), dp_axes)
        return out.reshape(bl, s, d).astype(xs.dtype), aux

    in_specs = (P(), P(ep_axis), P(ep_axis), P(ep_axis), P(b_shard))
    out_specs = (P(b_shard), P())
    fn = jax.shard_map(local_moe, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(params["router"], params["wi_gate"], params["wi_up"],
              params["wo"], x)
