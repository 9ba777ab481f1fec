"""Opportunistic Collaborative Learning (Lee et al. 2021).

Egocentric cycle per encounter: exchange - train - exchange - aggregate.
Device i sends its model to an encountered peer j; j trains i's model on
j's local data and returns it; i aggregates the returned model with its own.
Vectorized simplification (documented): each device picks its nearest
neighbor as the peer for the step.

Sharded populations: with a ``RingSpec`` the nearest-neighbor search runs
blockwise inside ``shard_map`` — each shard's (pos, area, active, batches)
block arrives by direct ring shift (``shift_perm``), and every local row
keeps a running lexicographic minimum over ``(distance^2, global peer
index)`` plus the winning peer's batch. The lexicographic tie-break makes
the result independent of ring order, so it equals the single-host
full-row ``argmin`` (first occurrence) exactly; since the per-row
train/aggregate math is shard-local, the sharded step is bitwise-equal to
single host on any mesh. With ``ring.prune`` the search shares gossip's
area-bitmask hop predicate: a pruned hop's block is all-``inf`` distance
(no same-area active pair), so skipping its transfer and its ``argmin``
update leaves ``met`` and every met row's winner unchanged — rows that met
no peer may carry different placeholder batches, but ``gamma * met = 0``
gates them out of the aggregate bitwise.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.baselines.gossip import RingSpec, _ring_need, _ring_shift
from repro.core.aggregation import batched_mix
from repro.kernels.encounter_mix import encounter_gate


def _block_d2(pos_r, area_r, act_r, row0, pos_v, area_v, act_v, col0):
    """Squared distances of local rows vs a visiting block, inf where the
    pair fails the shared non-distance gates (``encounter_gate``)."""
    d2, gate = encounter_gate(pos_r, area_r, act_r, row0,
                              pos_v, area_v, act_v, col0)
    return jnp.where(gate, d2, jnp.inf)


def _ring_nearest_peer(pos, area, active, batches, *, radius: float,
                       ring: RingSpec):
    """Cross-shard nearest-encounter search; returns (peer_batches, met)."""
    m_loc = pos.shape[0]
    n = ring.axis_size
    i = jax.lax.axis_index(ring.axis_name)
    row0 = i * m_loc
    act = (jnp.ones((m_loc,), bool) if active is None else active)
    orig = (pos, area, act, batches)

    def consume(carry, visiting, col0):
        best_d2, best_g, best_b = carry
        pos_v, area_v, act_v, batch_v = visiting
        d2 = _block_d2(pos, area, act, row0, pos_v, area_v, act_v, col0)
        d2 = jnp.where(d2 <= radius ** 2, d2, jnp.inf)
        j = jnp.argmin(d2, axis=1)                           # [m_loc]
        cand = jnp.min(d2, axis=1)
        cand_g = (col0 + j).astype(jnp.int32)
        better = (cand < best_d2) | ((cand == best_d2) & (cand_g < best_g))
        best_d2 = jnp.where(better, cand, best_d2)
        best_g = jnp.where(better, cand_g, best_g)
        cand_b = jax.tree.map(lambda l: l[j], batch_v)
        best_b = jax.tree.map(
            lambda nw, o: jnp.where(
                better.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, o),
            cand_b, best_b)
        return best_d2, best_g, best_b

    carry = (jnp.full((m_loc,), jnp.inf),
             jnp.full((m_loc,), jnp.iinfo(jnp.int32).max, jnp.int32),
             batches)                  # placeholder rows; met gates use
    carry = consume(carry, orig, row0)            # shift 0: local block
    if n > 1:
        need = _ring_need(area, act, ring) if ring.prune else None
        nxt = _ring_shift(orig, 1, ring, need)
        for s in range(1, n):
            blk = nxt
            if s + 1 < n:    # issue the next transfer before consuming
                nxt = _ring_shift(orig, s + 1, ring, need)
            col0 = ((i - s) % n) * m_loc
            if need is None:
                carry = consume(carry, blk, col0)
            else:
                carry = jax.lax.cond(
                    need[s],
                    lambda args, c0=col0: consume(args[0], args[1], c0),
                    lambda args: args[0], (carry, blk))
    best_d2, _, best_b = carry
    met = jnp.isfinite(best_d2).astype(jnp.float32)
    return best_b, met


def oppcl_step(models: Any, pos: jnp.ndarray, area: jnp.ndarray,
               batches: Any, train_fn: Callable, key, *,
               radius: float = 0.15, gamma: float = 0.5,
               active: Optional[jnp.ndarray] = None, backend: str = "ref",
               ring: Optional[RingSpec] = None, keys=None) -> Any:
    """One OppCL cycle over the population block.

    ``ring``/``keys`` follow the ``gossip_step`` contract (shard-local
    block + streamed neighbor search / externally supplied per-row train
    keys). ``backend`` is accepted for signature uniformity with
    ``gossip_step``; the peer search is D-free, so there is no kernel to
    select.
    """
    m = pos.shape[0]
    with jax.named_scope("mule_peer"):
        if ring is None:
            d2 = _block_d2(pos, area, active, 0, pos, area, active, 0)
            d2 = jnp.where(d2 <= radius ** 2, d2, jnp.inf)
            peer = jnp.argmin(d2, axis=1)                          # [M]
            met = jnp.isfinite(jnp.min(d2, axis=1)).astype(jnp.float32)
            peer_batches = jax.tree.map(lambda l: l[peer], batches)  # j's data
        else:
            peer_batches, met = _ring_nearest_peer(
                pos, area, active, batches, radius=radius, ring=ring)

    # peer j trains i's model on j's data (exchange-train), then
    # (exchange back - aggregate)
    with jax.named_scope("mule_train"):
        if keys is None:
            keys = jax.random.split(key, m)
        trained = jax.vmap(train_fn)(models, peer_batches, keys)
        return batched_mix(models, trained, gamma * met)
