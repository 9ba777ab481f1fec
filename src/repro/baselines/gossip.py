"""Gossip Learning (Hegedűs et al. 2019).

Per encounter: exchange-aggregate-train. Mobile devices within
``radius`` of each other in the same area exchange models, average with all
neighbors (masked row-normalized mixing), then train one local step.

The neighbor average is the fused ``encounter_mix`` op
(``repro.kernels.encounter_mix``): models flatten once to an [M, D] matrix
and one pass computes the distance-tested, row-normalized mix — the former
dense path (``encounter_matrix`` + per-leaf ``masked_group_mean``) survives
below only as the benchmark baseline it was replaced by.

Sharded populations: with a ``RingSpec`` the step runs inside ``shard_map``
over the mesh mule axis. Each shard holds a block of the population; hop
``s`` ``ppermute``s the original (pos, area, active, flattened models)
block straight from shard ``(i - s) % n`` (``shift_perm``), one
``encounter_block`` partial accumulated per hop, and the row normalization
happens once at the end — so no shard ever sees the full [M, M] matrix
either. Because the hops are independent shifts of the same block (not a
chained forward), the ring is locality-aware: each shard publishes a
32- or 64-bit area-set summary (one tiny psum per exchange), and every remote
hop whose source/destination area sets provably cannot intersect skips
both its payload ``ppermute`` and its block compute under ``lax.cond`` —
a pruned hop would have contributed exactly zero, so the pruned and
unpruned rings agree bitwise. The next hop's permute is issued before the
in-flight block is consumed (double buffering), and ``backend="pallas"``
routes each hop's block math through the per-hop tile kernel
(``encounter_block_hop``). A 1-shard ring is exactly the single-host
*ref* call, so the distributed engine is bitwise-equal to single host on
a 1-device mesh under the default ``enc_backend="ref"``.

Mules should be ordered by spatial bucket for the pruning to bite — see
``repro.core.distributed.bucket_mule_order`` (build-time ordering) and
``migrate_mules`` (the mid-run re-bucketing primitive).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import batched_mix, masked_group_mean
from repro.kernels.encounter_mix import (encounter_block,
                                         encounter_block_hop, encounter_mix,
                                         normalize_mix)

N_AREA_BITS = 32


@dataclasses.dataclass(frozen=True)
class RingSpec:
    """Mesh ring for cross-shard encounter search.

    ``axis_name`` is the shard_map mule axis; ``axis_size`` its static size
    (the ring unrolls one ``ppermute`` hop per shard). ``prune`` enables
    the area-bitmask hop pruning — exact, so it is on by default; the
    benchmarks flip it off to measure the dense ring. ``n_bits`` is the
    area-summary mask width: area ids fold with ``% n_bits``, so runs with
    more than ``n_bits`` distinct areas alias bits and lose pruning power
    (never soundness) — the drivers widen to 64 automatically when area
    ids overflow 32 (``DistributedConfig.ring_bits``).
    """
    axis_name: str
    axis_size: int
    prune: bool = True
    n_bits: int = N_AREA_BITS

    def perm(self) -> List[Tuple[int, int]]:
        return [(s, (s + 1) % self.axis_size) for s in range(self.axis_size)]

    def shift_perm(self, s: int) -> List[Tuple[int, int]]:
        """Permutation delivering shard j's block to shard (j + s) % n —
        i.e. after one ppermute every shard i holds shard (i - s) % n."""
        return [(j, (j + s) % self.axis_size)
                for j in range(self.axis_size)]


def area_bits(area: jnp.ndarray, active: Optional[jnp.ndarray] = None,
              n_bits: int = N_AREA_BITS) -> jnp.ndarray:
    """[m] int areas (+ optional [m] active mask) -> [n_bits] bool summary.

    Bit ``b`` is set iff some active row has ``area % n_bits == b``. Hash
    collisions (areas ``n_bits`` apart) can only *add* bits, so a predicate
    built on these summaries may keep a skippable hop but can never prune a
    hop whose blocks truly share an area.
    """
    hit = (area[:, None] % n_bits) == jnp.arange(n_bits)[None, :]
    if active is not None:
        hit = hit & active[:, None]
    return jnp.any(hit, axis=0)


def hops_needed(all_bits: jnp.ndarray) -> jnp.ndarray:
    """[n_shards, n_bits] per-shard area summaries -> [n_shards] bool.

    Entry ``s`` answers: does *any* shard's area set intersect the area set
    of its shift-``s`` ring source ``(i - s) % n``? (``roll(+s)`` aligns
    each row ``i`` with row ``(i - s) % n``.) Entry 0 — the shard-local
    block — is True whenever any shard has an active mule.
    """
    n = all_bits.shape[0]
    return jnp.stack([jnp.any(all_bits & jnp.roll(all_bits, s, axis=0))
                      for s in range(n)])


def ring_hop_mask(area: jnp.ndarray, active: Optional[jnp.ndarray],
                  n_shards: int,
                  n_bits: int = N_AREA_BITS) -> jnp.ndarray:
    """Host-side mirror of the in-ring pruning predicate.

    Splits the global ``area``/``active`` rows into ``n_shards`` equal
    blocks (the shard layout) and returns the [n_shards] bool hop mask the
    pruned ring computes — shared by the benchmark telemetry and the
    property tests so both exercise the exact predicate the ring runs.
    """
    m_loc = area.shape[0] // n_shards
    blocks = []
    for k in range(n_shards):
        sl = slice(k * m_loc, (k + 1) * m_loc)
        blocks.append(area_bits(jnp.asarray(area)[sl],
                                None if active is None
                                else jnp.asarray(active)[sl],
                                n_bits=n_bits))
    return hops_needed(jnp.stack(blocks))


def area_bit_collision_rate(area, n_bits: int = N_AREA_BITS) -> float:
    """Fraction of distinct area ids that share their summary bit with
    another distinct id under the ``% n_bits`` fold.

    0.0 means the bitmask separates every area (pruning at full power);
    anything above it measures how much the fold blunts the predicate —
    aliased areas can only *retain* hops, never prune a needed one, so
    this is a telemetry number, not a soundness concern. Recorded per run
    in the encounter-bench ring telemetry.
    """
    u = np.unique(np.asarray(area))
    if u.size == 0:
        return 0.0
    bits = u % n_bits
    _, counts = np.unique(bits, return_counts=True)
    collided = int(counts[counts > 1].sum())
    return float(collided) / float(u.size)


def _ring_need(area, act, ring: RingSpec) -> jnp.ndarray:
    """Replicated [axis_size] hop mask, computed in-ring via one psum.

    The per-shard bitmask is scattered into an [n, n_bits] table with a
    ``psum`` (rather than ``all_gather``) so the result is known-replicated
    and may gate a ``lax.cond`` whose true branch contains a collective.
    """
    n = ring.axis_size
    i = jax.lax.axis_index(ring.axis_name)
    bits = area_bits(area, act, n_bits=ring.n_bits)
    mine = ((jnp.arange(n) == i).astype(jnp.float32)[:, None]
            * bits.astype(jnp.float32)[None, :])
    all_bits = jax.lax.psum(mine, ring.axis_name) > 0
    return hops_needed(all_bits)


def _ring_shift(orig, s: int, ring: RingSpec, need):
    """ppermute ``orig`` around the ring by shift ``s``; when hop ``s`` is
    pruned the transfer itself is skipped (the untouched tuple flows into
    a consume that the same predicate also skips)."""
    def send(o):
        return jax.tree.map(
            lambda l: jax.lax.ppermute(l, ring.axis_name,
                                       ring.shift_perm(s)), o)
    if need is None:
        return send(orig)
    return jax.lax.cond(need[s], send, lambda o: o, orig)


def flatten_population(models: Any) -> Tuple[jnp.ndarray, Any]:
    """Stacked pytree [M, ...] -> (f32 [M, D] matrix, unflatten spec)."""
    leaves, treedef = jax.tree.flatten(models)
    m = leaves[0].shape[0]
    shapes = [l.shape[1:] for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
    return flat, (treedef, shapes, [l.dtype for l in leaves])


def unflatten_population(flat: jnp.ndarray, spec: Any) -> Any:
    treedef, shapes, dtypes = spec
    outs, off = [], 0
    for s, dt in zip(shapes, dtypes):
        n = int(np.prod(s)) if s else 1
        outs.append(flat[:, off:off + n]
                    .reshape((flat.shape[0],) + s).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, outs)


def encounter_matrix(pos: jnp.ndarray, area: jnp.ndarray, radius: float,
                     active: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """pos [M,2], area [M] -> symmetric bool [M,M] (no self).

    The retired dense path (kept as the ``run_encounter_bench`` baseline
    and for O(M^2)-tolerant callers). ``active`` ([M] bool, optional) drops
    switched-off mules from both sides of every encounter — a sleeping
    device neither initiates nor serves as a peer.
    """
    d2 = jnp.sum((pos[:, None] - pos[None, :]) ** 2, axis=-1)
    same_area = area[:, None] == area[None, :]
    enc = (d2 <= radius ** 2) & same_area
    if active is not None:
        enc = enc & active[:, None] & active[None, :]
    return enc & ~jnp.eye(pos.shape[0], dtype=bool)


def ring_encounter_mix(pos: jnp.ndarray, area: jnp.ndarray,
                       active: Optional[jnp.ndarray], flat: jnp.ndarray, *,
                       radius: float, ring: RingSpec,
                       backend: str = "ref",
                       block_m: Optional[int] = None,
                       block_d: Optional[int] = None):
    """Blockwise ``encounter_mix`` across the mesh ring (inside shard_map).

    All arguments are this shard's block ([m_loc, ...]). Hop ``s`` matches
    the local rows against the block ``shift_perm(s)``-permuted straight
    from shard ``(i - s) % n`` — the same per-hop partials (in the same
    accumulation order) as a chained single-shift ring, but with hops
    independent of each other, which buys three things: with ``ring.prune``
    each remote hop's payload permute *and* block compute sit under a
    ``lax.cond`` keyed on the per-shard area bitmasks; hop ``s+1``'s
    permute is issued before hop ``s``'s block is consumed (double
    buffering, so the transfer overlaps the compute); and ``backend``
    selects the per-hop block math (``encounter_block_hop`` — ref einsum
    or the tiled Pallas hop kernel). Returns the local rows'
    (mix [m_loc, D], mass [m_loc]).
    """
    m_loc = flat.shape[0]
    n = ring.axis_size
    i = jax.lax.axis_index(ring.axis_name)
    row0 = i * m_loc
    act = (jnp.ones((m_loc,), bool) if active is None else active)
    orig = (pos, area, act, flat)

    def hop(visiting, col0):
        pos_v, area_v, act_v, flat_v = visiting
        return encounter_block_hop(pos, area, act, row0, pos_v, area_v,
                                   act_v, col0, flat_v, radius,
                                   backend=backend, block_m=block_m,
                                   block_d=block_d)

    acc, mass = hop(orig, row0)                    # shift 0: local block
    if n > 1:
        need = _ring_need(area, act, ring) if ring.prune else None

        def consume(blk, s):
            col0 = ((i - s) % n) * m_loc
            if need is None:
                return hop(blk, col0)
            return jax.lax.cond(
                need[s], lambda b: hop(b, col0),
                lambda b: (jnp.zeros_like(acc), jnp.zeros_like(mass)), blk)

        nxt = _ring_shift(orig, 1, ring, need)
        for s in range(1, n):
            blk = nxt
            if s + 1 < n:       # issue the next transfer before consuming
                nxt = _ring_shift(orig, s + 1, ring, need)
            p_acc, p_mass = consume(blk, s)
            acc = acc + p_acc
            mass = mass + p_mass
    return normalize_mix(acc, mass), mass


def gossip_step(models: Any, pos: jnp.ndarray, area: jnp.ndarray,
                batches: Any, train_fn: Callable, key, *,
                radius: float = 0.15, gamma: float = 0.5,
                active: Optional[jnp.ndarray] = None, backend: str = "ref",
                ring: Optional[RingSpec] = None, keys=None) -> Any:
    """One gossip exchange-aggregate-train step over the population block.

    ``ring=None`` runs single-host over the full population (``backend``
    selects ref vs the tiled Pallas kernel); with a ``RingSpec`` the step
    is the shard-local block of a shard_map'd population and neighbors
    stream around the mesh ring. ``keys`` overrides the per-device training
    keys ([M, 2]) — the distributed engine passes the global-split local
    slice so sharded draws match single host row for row.
    """
    with jax.named_scope("mule_peer"):
        flat, spec = flatten_population(models)
        if ring is None:
            mixed, mass = encounter_mix(pos, area, active, flat,
                                        radius=radius, backend=backend)
        else:
            mixed, mass = ring_encounter_mix(pos, area, active, flat,
                                             radius=radius, ring=ring,
                                             backend=backend)
        neigh_mean = unflatten_population(mixed, spec)
        met = (mass > 0).astype(jnp.float32)
        models = batched_mix(models, neigh_mean, gamma * met)       # aggregate
    with jax.named_scope("mule_train"):
        if keys is None:
            keys = jax.random.split(key, mass.shape[0])
        trained = jax.vmap(train_fn)(models, batches, keys)         # train
        return batched_mix(models, trained, met)                    # only on encounter


def gossip_step_dense(models: Any, pos: jnp.ndarray, area: jnp.ndarray,
                      batches: Any, train_fn: Callable, key, *,
                      radius: float = 0.15, gamma: float = 0.5,
                      active: Optional[jnp.ndarray] = None) -> Any:
    """The retired dense gossip step: [M, M] matrix + per-leaf group mean.

    Benchmark baseline only (``benchmarks/engine_micro.run_encounter_bench``
    times it against the fused path); note it normalizes the encounter
    matrix *before* the per-leaf matmuls, so it differs from ``gossip_step``
    in float rounding, not semantics.
    """
    enc = encounter_matrix(pos, area, radius, active).astype(jnp.float32)
    neigh_mean, mass = masked_group_mean(models, enc)
    met = (mass > 0).astype(jnp.float32)
    models = batched_mix(models, neigh_mean, gamma * met)
    keys = jax.random.split(key, mass.shape[0])
    trained = jax.vmap(train_fn)(models, batches, keys)
    return batched_mix(models, trained, met)
