"""Distributed ML Mule: the population engine under shard_map.

Mapping (DESIGN.md Sec 2):
- the mule population axis shards over the mesh ``data`` axis;
- physical areas map to pods (the paper's two near-isolated cities);
- fixed-device models are small and replicated; each shard computes its
  mules' aggregation *contributions* locally and a single ``psum`` combines
  them — the paper's many tiny peer-to-peer exchanges become one fused
  segment-reduce + all-reduce per step;
- the rare cross-area mule (0.715% in the Foursquare data) is a
  ``collective_permute`` of mule state across the ``pod`` axis.

``make_distributed_method_step`` builds the shard-local one-step update the
*scan* engine replays: same ``(state, info, batches, key) -> state``
signature as the single-host ``make_method_step``, both thin wrappers over
the one ``repro.core.method_program`` table. ML Mule's space exchange
lowers to the fused segment-reduce + psum collective schedule; the
peer-encounter baselines (gossip/oppcl/mlmule+gossip) lower to a ring
``ppermute`` exchange that streams population blocks around the mesh mule
axis — so every ``METHODS_MOBILE`` method shards. The whole replay —
collectives included — then runs as one ``lax.scan`` under ``shard_map``
(``repro.scenarios.run_population_distributed``), so an experiment is a
single XLA program instead of thousands of per-step dispatches.  The old
per-step ``make_distributed_step`` — a dense one-hot segment-reduce per
model leaf — has been deleted outright: the fused ``encounter_mix``
schedule (Pallas-tiled on TPU, its bitwise reference elsewhere) is the
*only* encounter path on the distributed engines, and the per-step
dispatch baseline the benchmarks time is the scan engine driven one
chunk per step (``run_population_distributed_loop``).

Freshness semantics: the scan engine closes the formerly documented
mean/std deviation — with ``FreshnessConfig.stat == "median"`` (default)
delivered ages feed an associative histogram sketch whose per-step shard
contributions merge under the same psum as the aggregation, recovering the
paper's Sec 3.1 median/MAD to bin accuracy (``repro.core.freshness``).
``stat == "meanstd"`` keeps the legacy per-step mean/std EMA, reading
alpha/beta from ``FreshnessConfig`` like every other engine path.

Multi-process: every collective here is also run under ``jax.distributed``
(``launch.multiprocess`` bring-up, gloo CPU backend in tests/benches).
Float cross-shard reductions go through ``ordered_psum`` — gloo and
single-process XLA reduce in different orders, and an unordered ``psum``
would drift ULPs off the pinned cross-topology bitwise parity. Integer
reductions (counts, ring need-masks, the re-bucketing area gather) are
exact under any order and stay on ``lax.psum``.

Two collective schedules are provided (Perf hillclimb lever):
- ``cross_pod=True``  (baseline): F fixed devices replicated everywhere;
  contributions psum over ("pod", "data") — simple, but the [F, D] partial
  sums cross the pod boundary every step.
- ``cross_pod=False`` (optimized): fixed devices are pod-local (4 per pod);
  psum only over "data"; zero steady-state inter-pod traffic, matching the
  paper's observation that areas are nearly isolated.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.freshness import (FreshnessConfig, age_histogram,
                                  init_freshness_sketch)
from repro.core.population import PopulationConfig


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    pop: PopulationConfig
    data_axis: str = "data"
    pod_axis: str = "pod"          # "" -> single-pod mesh
    cross_pod: bool = True         # collective schedule (see module docstring)
    # area-bitmask hop pruning of the peer-exchange ring (exact — a pruned
    # hop would contribute nothing; False measures the dense ring)
    ring_prune: bool = True
    # ring area-bitmask width. 0 = auto: 32 bits, widened to 64 when the
    # run's max area id needs it (>32 areas alias under a 32-bit fold and
    # quietly stop pruning). The drivers resolve 0 to a concrete width
    # before the value enters any jit cache key.
    ring_bits: int = 0
    # mid-run re-bucketing: every `rebucket_every` steps (chunk-aligned on
    # the streamed engine) the compiled replay emits the psum'd fraction of
    # mules whose current area drifted off their bucket area; when it
    # crosses `rebucket_threshold` the driver recomputes the bucket order
    # and permutes the full live mule state + in-flight colocation columns
    # through the mesh. 0 = off (build-time bucketing only, PR 7 behavior).
    rebucket_every: int = 0
    rebucket_threshold: float = 0.25


def _tree_mix(a, b, gamma):
    def mix(x, y):
        g = jnp.reshape(gamma, gamma.shape + (1,) * (x.ndim - gamma.ndim))
        return (1.0 - g) * x + g * y
    return jax.tree.map(mix, a, b)


def ordered_psum(x, axis_name):
    """Order-deterministic float ``psum``: all_gather + rank-order fold.

    ``lax.psum`` leaves the float reduction order to the backend — XLA's
    single-process all-reduce and the gloo cross-process one disagree,
    so a raw psum breaks the engines' cross-topology bitwise pins (the
    same run over 1 or N processes). ``all_gather`` is pure data
    movement (bitwise-safe on both), and a left-to-right fold over the
    gathered shards fixes the reduction order as a function of the mesh
    axis alone. Axis sizes here are ring-scale, so the serial fold is
    free next to the payload it reduces. Integer reductions are exact
    under any order — keep those on ``lax.psum``.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    g = jax.lax.all_gather(x, axes, axis=0, tiled=False)
    return jax.tree.map(
        lambda l: functools.reduce(
            lambda a, b: a + b, [l[i] for i in range(l.shape[0])]), g)


def ordered_pmean(x, axis_name):
    """``ordered_psum`` divided by the axis size — deterministic pmean."""
    s = ordered_psum(x, axis_name)
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n = 1
    for ax in axes:
        n = n * jax.lax.psum(1, ax)
    return jax.tree.map(lambda l: l / n, s)


@functools.lru_cache(maxsize=8)
def _bucket_order_program(mesh: Mesh, data_axis: str, n_shards: int,
                          m_loc: int):
    """Compiled replicated stable argsort of the sharded area vector."""
    def order_fn(a_loc):
        i = jax.lax.axis_index(data_axis)
        placed = jax.lax.dynamic_update_slice(
            jnp.zeros((n_shards * m_loc,), jnp.int32),
            a_loc.astype(jnp.int32), (i * m_loc,))
        full = jax.lax.psum(placed, data_axis)        # int32: exact
        order = jnp.argsort(full, stable=True).astype(jnp.int32)
        return order, full

    return jax.jit(jax.shard_map(
        order_fn, mesh=mesh, in_specs=(P(data_axis),),
        out_specs=(P(), P()), check_vma=False))


def global_bucket_order(area_last, mesh, data_axis: str = "data"):
    """Multi-host-safe bucket order of the current (sharded) area vector.

    The PR 9 drift swap argsorted ``np.asarray(area_last)`` on the host —
    fine while one process owned the whole [M] vector, impossible once it
    shards across processes. Here every shard contributes its block
    through an exact integer psum (dynamic placement into the zeroed
    global vector), each process argsorts the identical replicated copy
    inside the compiled program, and the replicated ``(order, area)``
    pair comes back readable on every process. Stable argsort matches
    ``np.argsort(kind="stable")`` exactly, so single-process rebucketing
    decisions (and their bitwise pins) are unchanged.
    """
    m = int(area_last.shape[0])
    n_shards = int(mesh.shape[data_axis])
    fn = _bucket_order_program(mesh, data_axis, n_shards, m // n_shards)
    return fn(area_last)


def init_distributed_freshness(n_fixed: int, cfg: FreshnessConfig):
    """Replicated freshness state for the scan engine, per ``cfg.stat``."""
    if cfg.stat == "median":
        return init_freshness_sketch(n_fixed, cfg)
    if cfg.stat == "meanstd":
        return {"threshold": jnp.full((n_fixed,), cfg.init_threshold,
                                      jnp.float32)}
    raise ValueError(f"unknown freshness stat {cfg.stat!r}; "
                     "expected 'median' or 'meanstd'")


def to_distributed_state(state: Dict[str, Any],
                         dcfg: DistributedConfig) -> Dict[str, Any]:
    """Convert an ``init_population`` state for the distributed engine.

    Swaps the exact ring-buffer freshness state for the collective-friendly
    variant ``dcfg.pop.freshness.stat`` selects, carrying the learned
    threshold over and (for the sketch) binning the ring's resident ages so
    no history is lost at the handoff.
    """
    cfg = dcfg.pop.freshness
    fresh = init_distributed_freshness(dcfg.pop.n_fixed, cfg)
    old = state.get("fresh", {})
    if "threshold" in old:
        fresh["threshold"] = old["threshold"]
    if cfg.stat == "median" and "ages" in old:
        valid = old["ages"] < 1e29
        fresh["hist"] = age_histogram(old["ages"],
                                      valid.astype(jnp.float32), cfg)
        fresh["count"] = old["count"]
    return {**state, "fresh": fresh}


def make_distributed_method_step(method: str, train_fn: Callable,
                                 dcfg: DistributedConfig,
                                 mesh: Mesh = None) -> Callable:
    """Shard-local one-step update for the distributed scan engine.

    Thin wrapper over the one ``repro.core.method_program`` table (the
    same programs ``make_method_step`` lowers single-host), compiled to the
    shard_map lowering: same ``step(state, info, batches, key) -> state``
    signature, but every array with a leading mule axis is the *local
    shard* of the population ([M_loc, ...], M_loc = n_mules / data-axis
    size), ``info`` additionally carries the shard-local ``"area"`` block,
    and the step must run inside ``shard_map`` over ``dcfg.data_axis``.
    ``state`` follows the ``to_distributed_state`` layout: mule_models /
    mule_ts sharded, fixed_models/fresh/t replicated.

    All five ``METHODS_MOBILE`` lower: ``mlmule`` runs the fused
    segment-reduce + single-psum collective schedule; the peer-encounter
    baselines (gossip / oppcl / the mlmule+gossip hybrid) stream each
    shard's (pos, area, active, payload) block around the mesh mule axis
    with a ring ``ppermute`` (``mesh`` is required to size the ring);
    ``local`` needs no collective at all.

    Key discipline mirrors the single-host engine exactly: fixed-mode
    training splits the replicated key over ``n_fixed``; every per-mule
    draw splits it over the *global* ``n_mules`` and slices the local
    block, so per-mule draws are identical to a single-host run regardless
    of shard count. Mule batches produced replicated (a batch callable
    returning full ``[n_mules, ...]`` arrays) are sliced the same way;
    batches already shard-local (stacked sharded inputs) pass through.

    Churn: ``info["active"]`` ([M_loc] bool, sharded like ``fixed_id``)
    masks switched-off mules with the single-host semantics — mlmule ANDs
    it into the delivery mask before the fused reduction, peer exchanges
    drop inactive mules from both sides of the streamed encounter test,
    and local/mobile training where-selects old models back in.
    """
    from repro.core.method_program import (compile_distributed_step,
                                           get_program)
    ring_size = (int(mesh.shape[dcfg.data_axis]) if mesh is not None
                 else None)
    return compile_distributed_step(get_program(method), train_fn, dcfg,
                                    ring_size=ring_size)


def migrate_mules(mule_models: Any, move_mask: jnp.ndarray, mesh: Mesh,
                  pod_axis: str = "pod", data_axis: str = "data"):
    """Cross-area mule transport: swap flagged mule slots with the next pod.

    move_mask: [M] bool (sharded over data). A flagged mule's model is sent
    to the same slot on the next pod (ring collective_permute) — the paper's
    inter-city traveler (0.715% of Foursquare check-ins). Applying the swap
    ``n_pods`` times walks a slot around the whole ring back to its origin,
    so migrations round-trip bitwise (pinned by ``tests/test_distributed``);
    ``migrate_mule_state`` lifts this to the full live-state pytree; the
    scan drivers fire it between chunks when ``rebucket_every`` is set.
    """
    n_pods = mesh.shape[pod_axis]
    perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]

    def swap(models, mask):
        def one(leaf):
            recv = jax.lax.ppermute(leaf, pod_axis, perm)
            m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(m, recv, leaf)
        return jax.tree.map(one, models)

    sharded = jax.shard_map(
        swap, mesh=mesh,
        in_specs=(P(data_axis), P(data_axis)),
        out_specs=P(data_axis),
        check_vma=False)
    return sharded(mule_models, move_mask)


def migrate_mule_state(state: Dict[str, Any], move_mask: jnp.ndarray,
                       mesh: Mesh, pod_axis: str = "pod",
                       data_axis: str = "data") -> Dict[str, Any]:
    """``migrate_mules`` over the *full* live-state pytree.

    ``migrate_mules`` only ever saw model leaves; mid-run re-bucketing has
    to move everything a mule owns — models, delivery timestamps, freshness
    carry, optimizer slots — or the swapped-in mule trains against a
    stranger's history. Every sharded ``mule*`` leaf rides the same pod-ring
    ``collective_permute``; replicated leaves (fixed models, freshness
    sketch, scalar clock) pass through untouched. Applying the swap
    ``n_pods`` times round-trips bitwise, same as the model-only primitive.
    """
    moving = {k: v for k, v in state.items()
              if k.startswith("mule") and v is not None}
    if not moving:
        return dict(state)
    swapped = migrate_mules(moving, move_mask, mesh,
                            pod_axis=pod_axis, data_axis=data_axis)
    return {**state, **swapped}


_row_gather = jax.jit(lambda l, o: l[jnp.asarray(o)])


def bucket_mule_order(area) -> np.ndarray:
    """Area ids -> [M] permutation grouping mules by spatial bucket.

    Accepts the static [M] contract or a time-varying [T, M] trace (the
    mobility scenarios that motivate re-bucketing) — build-time bucketing
    uses the t=0 row; the re-bucketing drivers pass the current row
    explicitly. Stable sort, so the order within a bucket (and the
    identity when every mule shares one area) is preserved. Applying this
    at colocation build time makes the population's shard blocks
    area-contiguous, which is what lets the ring's area-bitmask predicate
    prune remote hops — interleaved assignments leave every area on every
    shard and nothing prunable. Mid-run, the scan drivers re-apply it
    whenever the compiled replay's drift scalar crosses
    ``DistributedConfig.rebucket_threshold``.
    """
    a = np.asarray(area)
    if a.ndim == 2:
        a = a[0]
    return np.argsort(a, kind="stable")


def reorder_colocation(colocation: Dict[str, Any],
                       order: np.ndarray) -> Dict[str, Any]:
    """Apply a mule permutation to every per-mule colocation column.

    Works on any colocation dict whose values are [T, M] (fixed_id /
    exchange / active / time-varying area / pos [T, M, 2]) or [M] (static
    area) arrays; the mule axis is the one matching ``len(order)``.
    """
    order = np.asarray(order)

    def one(v):
        a = np.asarray(v)
        if a.ndim >= 2 and a.shape[1] == order.shape[0]:
            return a[:, order]
        if a.ndim >= 1 and a.shape[0] == order.shape[0]:
            return a[order]
        return a
    return {k: one(v) for k, v in colocation.items()}


def reorder_mule_state(state: Dict[str, Any], order) -> Dict[str, Any]:
    """Apply a mule permutation to the per-mule state leaves.

    Every ``mule*`` entry — models, timestamps, and any future per-mule
    carry (freshness, optimizer slots) — has its rows follow their
    colocation columns (``reorder_colocation``), so a bucket-ordered run is
    the same simulation with mules renumbered; replicated leaves pass
    through. Mid-run re-bucketing relies on this covering the *full* live
    state: a key it missed would silently cross-wire a mule's history.
    The gather runs jitted so it also applies to state sharded across
    processes (eager gathers reject multi-host arrays); on one process
    the jitted gather is bitwise the old eager one.
    """
    order = np.asarray(order)
    out = dict(state)
    for k in out:
        if k.startswith("mule") and out[k] is not None:
            out[k] = jax.tree.map(lambda l: _row_gather(l, order), out[k])
    return out


def bucket_locality_fraction(area, n_shards: int) -> float:
    """Fraction of same-area ordered mule pairs that are shard-local under
    the equal-block layout of ``area`` over ``n_shards`` shards.

    Same-area pairs are exactly the candidate encounters the ring must
    cover, so this is the share of encounter work the shard-local hop can
    serve — the benchmark's bucket-locality telemetry. 1.0 when there are
    no same-area pairs at all. Shards are the ``np.array_split`` blocks, so
    a population size that does not divide ``n_shards`` is handled exactly
    (the old equal-block slicing silently dropped the ragged tail, counting
    its pairs as neither local nor remote).
    """
    a = np.asarray(area)
    if a.ndim == 2:
        a = a[0]
    local = total = 0
    blocks = np.array_split(a, n_shards)
    for u in np.unique(a):
        c = int((a == u).sum())
        total += c * (c - 1)
        for blk in blocks:
            ck = int((blk == u).sum())
            local += ck * (ck - 1)
    return float(local) / float(total) if total else 1.0
