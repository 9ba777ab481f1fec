"""Model aggregation primitives over stacked-pytree populations.

A population of P models is a pytree whose leaves have a leading P axis.
``masked_group_mean`` is ML Mule's aggregation hot spot: every fixed device
averages the (freshness-filtered, dwell-weighted) models delivered by its
co-located mules — a [F, M] × [M, D] reduce over every parameter. The
Pallas ``mule_agg`` kernel implements the fused tiled version; the jnp path
is the oracle and CPU fallback.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def weighted_average(models: Any, weights: jnp.ndarray) -> Any:
    """models: stacked pytree [P, ...]; weights: [P] (need not sum to 1)."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)

    def avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(leaf * wb, axis=0)

    return jax.tree.map(avg, models)


def pairwise_mix(a: Any, b: Any, gamma) -> Any:
    """a <- (1-gamma) a + gamma b; gamma scalar or broadcastable per-leaf."""
    return jax.tree.map(lambda x, y: (1.0 - gamma) * x + gamma * y, a, b)


def batched_mix(a: Any, b: Any, gamma: jnp.ndarray) -> Any:
    """Stacked [P,...] mix with per-member gamma [P]."""
    def mix(x, y):
        g = gamma.reshape((-1,) + (1,) * (x.ndim - 1))
        return (1.0 - g) * x + g * y

    return jax.tree.map(mix, a, b)


def prox_mix(local: Any, incoming: Any, gamma, mu: float = 0.1) -> Any:
    """FedProx-style aggregation (paper Sec 3.1 lists FedProx/FedDyn/SCAFFOLD
    as drop-in replacements): the mix is pulled toward the local model by a
    proximal term — equivalent to mixing with an effective rate
    gamma' = gamma / (1 + mu), which damps drift from stale mules."""
    eff = gamma / (1.0 + mu)
    return pairwise_mix(local, incoming, eff)


def quality_weights(losses: jnp.ndarray, temperature: float = 1.0) -> jnp.ndarray:
    """Model-quality aggregation weights (paper cites IDML [44]): softmax of
    negative validation losses — better snapshots count more."""
    return jax.nn.softmax(-losses / jnp.maximum(temperature, 1e-6))


def masked_group_mean(models: Any, assign: jnp.ndarray, *,
                      backend: str = "ref") -> Any:
    """Weighted group means: out[f] = sum_m A[f,m] models[m] / sum_m A[f,m].

    models: stacked pytree [M, ...]; assign: [F, M] non-negative weights
    (zero = not delivering to that fixed device). Rows with zero mass return
    zeros — callers mask on ``row_mass``.
    Returns (grouped pytree [F, ...], row_mass [F]).
    """
    mass = jnp.sum(assign, axis=1)                       # [F]
    norm = assign / jnp.maximum(mass, 1e-12)[:, None]    # [F, M]

    if backend in ("pallas", "interpret"):
        from repro.kernels.mule_agg.ops import mule_agg
        leaves, treedef = jax.tree.flatten(models)
        shapes = [l.shape[1:] for l in leaves]
        sizes = [int(jnp.prod(jnp.array(s))) if s else 1 for s in shapes]
        flat = jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves], axis=1)
        out = mule_agg(norm.astype(jnp.float32), flat,
                       interpret=True if backend == "interpret" else None)
        outs, off = [], 0
        for s, n, l in zip(shapes, sizes, leaves):
            outs.append(out[:, off:off + n].reshape((out.shape[0],) + s).astype(l.dtype))
            off += n
        return jax.tree.unflatten(treedef, outs), mass

    def agg(leaf):
        flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        out = norm.astype(jnp.float32) @ flat
        return out.reshape((assign.shape[0],) + leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree.map(agg, models), mass
