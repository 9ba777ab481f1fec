"""One method table: every mobile-protocol method as a declarative program.

The engine used to keep two hand-maintained dispatch tables — the
single-host ``make_method_step`` and the distributed
``make_distributed_method_step`` — that had to agree method by method on
cadence, key discipline, and churn semantics, and that drifted in coverage
(the peer-encounter baselines never made it into the distributed table).
``MethodProgram`` replaces both: a method *declares* its per-step pieces
once, and one compiler lowers the declaration to either engine, so the two
lanes cannot drift by construction.

A program is three optional pieces, executed in this order each step:

- ``space_exchange``  — the ML Mule space-mediated cycle (deliver →
  freshness filter → dwell-weighted segment-reduce at fixed devices →
  train → send back). Lowering: single host runs ``population_step``;
  distributed runs the fused collective schedule (every per-step reduction
  packed into ONE ``psum``).
- ``peer_exchange``   — a device-to-device encounter op (``"gossip"`` |
  ``"oppcl"``), fired at the ``peer_every`` cadence (paper Sec 4.3.1: a
  peer hand-off costs 3 steps) as a ``lax.cond`` on the step index, keyed
  with ``fold_in(key, peer_key_fold)`` when riding alongside a space
  exchange. Lowering: single host calls the baseline step over the full
  population (the fused ``encounter_mix`` op); distributed wraps it in a
  ring ``ppermute`` exchange that streams each shard's (pos, area, active,
  payload) block around the mesh mule axis (``RingSpec``), so the search
  crosses shards without ever gathering the population.
- ``local_train``     — one local step on the training side (per
  ``cfg.mode``), no communication.

Activity-mask semantics are part of the contract, not per-method code: the
space exchange folds ``info["active"]`` into its delivery mask, peer
exchanges drop inactive mules from both sides of the encounter test and
``apply_activity_mask`` carries their models bitwise, and local training
where-selects old leaves back in.

Adding method #6
----------------
Add one ``MethodProgram`` entry (and the name to
``repro.core.population.METHODS_MOBILE``); both engines, the sweep lanes,
and the jit cache pick it up with no further dispatch code. A hybrid like
``mlmule+gossip`` is just ``space_exchange=True, peer_exchange="gossip",
peer_key_fold=1``; a faster-cadence gossip is ``peer_every=1``. Pieces that
don't exist yet (a new exchange op) plug in by extending ``_PEER_STEPS``
with a function of the ``gossip_step`` signature — the compiler treats the
op as data. ``tests/test_method_program.py`` exercises exactly this path
with a synthetic sixth method.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.gossip import N_AREA_BITS, RingSpec, gossip_step
from repro.baselines.local_only import local_step
from repro.baselines.oppcl import oppcl_step
from repro.core.freshness import age_bin_onehot, sketch_push_and_update
from repro.core.population import (METHODS_MOBILE, PopulationConfig, TrainFn,
                                   apply_activity_mask, population_step)


@dataclasses.dataclass(frozen=True)
class MethodProgram:
    """Declarative per-step pieces of one mobile-protocol method."""
    name: str
    space_exchange: bool = False        # ML Mule share-aggregate cycle
    peer_exchange: Optional[str] = None  # None | "gossip" | "oppcl"
    peer_every: int = 3                  # cadence: fires at t % k == k - 1
    peer_key_fold: Optional[int] = None  # fold_in(key, n) for the peer draw
    local_train: bool = False            # per-device local step, no comms


METHOD_PROGRAMS: Dict[str, MethodProgram] = {
    "mlmule": MethodProgram("mlmule", space_exchange=True),
    "gossip": MethodProgram("gossip", peer_exchange="gossip"),
    "oppcl": MethodProgram("oppcl", peer_exchange="oppcl"),
    "local": MethodProgram("local", local_train=True),
    "mlmule+gossip": MethodProgram("mlmule+gossip", space_exchange=True,
                                   peer_exchange="gossip", peer_key_fold=1),
}

_PEER_STEPS: Dict[str, Callable] = {"gossip": gossip_step, "oppcl": oppcl_step}


def get_program(method: str) -> MethodProgram:
    if method not in METHOD_PROGRAMS:
        raise ValueError(f"unknown method {method!r}; "
                         f"expected one of {METHODS_MOBILE}")
    return METHOD_PROGRAMS[method]


# ---------------------------------------------------------------------------
# single-host lowering
# ---------------------------------------------------------------------------


def compile_step(program: MethodProgram, train_fn: TrainFn,
                 cfg: PopulationConfig, area: jnp.ndarray) -> Callable:
    """Lower a program to the single-host scan step.

    Uniform signature ``step(state, info, batches, key) -> state`` with
    ``info`` carrying ``fixed_id``/``exchange``/``pos``/``t`` (and
    optionally ``active``); ``area`` is the per-mule area vector the
    peer-encounter ops need. On mobility scenarios whose area is a
    time-varying [T, M] trace, the scan threads the current row through
    ``info["area"]`` instead and the closed-over ``area`` is only the
    fallback. Semantics are bitwise-pinned to the per-step reference
    driver (``repro.scenarios.run_population_loop``).
    """
    peer_fn = (_PEER_STEPS[program.peer_exchange]
               if program.peer_exchange else None)
    if cfg.mode == "fixed":
        local_side, local_bkey = "fixed_models", "fixed"
    else:
        local_side, local_bkey = "mule_models", "mule"

    def step(st, info, batches, key):
        if program.space_exchange:
            st = population_step(st, info, batches, train_fn, cfg, key)
        if program.local_train:
            with jax.named_scope("mule_train"):
                trained = local_step(st[local_side], batches[local_bkey],
                                     train_fn, key)
                if local_side == "mule_models":
                    trained = apply_activity_mask(info.get("active"),
                                                  trained, st[local_side])
            st = {**st, local_side: trained}
        if peer_fn is not None:
            kp = (key if program.peer_key_fold is None
                  else jax.random.fold_in(key, program.peer_key_fold))
            act = info.get("active")

            def exchange(models):
                new = peer_fn(models, info["pos"], info.get("area", area),
                              batches["mule"], train_fn, kp, active=act,
                              backend=cfg.enc_backend)
                return apply_activity_mask(act, new, models)

            k = program.peer_every
            with jax.named_scope("mule_peer"):
                models = jax.lax.cond(info["t"] % k == k - 1, exchange,
                                      lambda m: m, st["mule_models"])
            st = {**st, "mule_models": models}
        return st

    return step


# ---------------------------------------------------------------------------
# distributed (shard_map) lowering
# ---------------------------------------------------------------------------


def _local_block(dcfg, leaf, m_loc):
    """Slice this shard's mule rows from a replicated [M, ...] array."""
    if leaf.shape[0] == m_loc:
        return leaf                           # already shard-local
    i = jax.lax.axis_index(dcfg.data_axis)
    return jax.lax.dynamic_slice_in_dim(leaf, i * m_loc, m_loc, axis=0)


def _mule_train_keys(dcfg, key, m_loc):
    """Global split + shard slice: per-mule draws match single host."""
    return _local_block(dcfg, jax.random.split(key, dcfg.pop.n_mules), m_loc)


def compile_distributed_step(program: MethodProgram, train_fn: Callable,
                             dcfg, *, ring_size: Optional[int] = None
                             ) -> Callable:
    """Lower a program to the shard-local distributed scan step.

    Same ``(state, info, batches, key) -> state`` signature, but every
    array with a leading mule axis is this shard's block and the step must
    run inside ``shard_map`` over ``dcfg.data_axis``; ``info`` additionally
    carries the shard-local ``"area"`` block. ``ring_size`` is the static
    data-axis size the peer-exchange ring unrolls over (required for peer
    programs; the engines read it off the mesh). ``dcfg.ring_prune``
    toggles the ring's exact area-bitmask hop pruning, and
    ``cfg.enc_backend`` selects the per-hop block math
    (``encounter_block_hop``), mirroring the single-host lowering.

    Key discipline mirrors the single-host lowering exactly: fixed-mode
    training splits the replicated key over ``n_fixed``; every per-mule
    draw (mobile training, peer-exchange training) splits it over the
    *global* ``n_mules`` and slices the local block, so sharded runs equal
    single-host runs row for row regardless of shard count.
    """
    cfg = dcfg.pop
    if program.peer_exchange and ring_size is None:
        raise ValueError(
            f"method {program.name!r} needs the mesh to size its ring "
            "exchange; pass mesh= to make_distributed_method_step")

    space_step = (_space_exchange_distributed(train_fn, dcfg)
                  if program.space_exchange else None)
    peer_fn = (_PEER_STEPS[program.peer_exchange]
               if program.peer_exchange else None)

    def step(st, info, batches, key):
        if space_step is not None:
            st = space_step(st, info, batches, key)
        if program.local_train:
            with jax.named_scope("mule_train"):
                if cfg.mode == "fixed":
                    keys = jax.random.split(key, cfg.n_fixed)
                    trained = jax.vmap(train_fn)(st["fixed_models"],
                                                 batches["fixed"], keys)
                    st = {**st, "fixed_models": trained}
                else:
                    m_loc = info["fixed_id"].shape[0]
                    mb = jax.tree.map(
                        lambda l: _local_block(dcfg, l, m_loc),
                        batches["mule"])
                    keys = _mule_train_keys(dcfg, key, m_loc)
                    trained = jax.vmap(train_fn)(st["mule_models"], mb, keys)
                    trained = apply_activity_mask(info.get("active"),
                                                  trained, st["mule_models"])
                    st = {**st, "mule_models": trained}
        if peer_fn is not None:
            kp = (key if program.peer_key_fold is None
                  else jax.random.fold_in(key, program.peer_key_fold))
            act = info.get("active")
            m_loc = info["fixed_id"].shape[0]
            ring = RingSpec(dcfg.data_axis, ring_size,
                            prune=getattr(dcfg, "ring_prune", True),
                            n_bits=(getattr(dcfg, "ring_bits", 0)
                                    or N_AREA_BITS))

            def exchange(models):
                # key split and batch slice stay inside the branch so the
                # ~(k-1)/k off-cadence steps pay nothing for them
                mb = jax.tree.map(lambda l: _local_block(dcfg, l, m_loc),
                                  batches["mule"])
                keys = _mule_train_keys(dcfg, kp, m_loc)
                new = peer_fn(models, info["pos"], info["area"], mb,
                              train_fn, kp, active=act,
                              backend=cfg.enc_backend, ring=ring, keys=keys)
                return apply_activity_mask(act, new, models)

            k = program.peer_every
            with jax.named_scope("mule_peer"):
                models = jax.lax.cond(info["t"] % k == k - 1, exchange,
                                      lambda m: m, st["mule_models"])
            st = {**st, "mule_models": models}
        return st

    return step


def _space_exchange_distributed(train_fn: Callable, dcfg) -> Callable:
    """The ML Mule cycle with the fused segment-reduce + ONE psum schedule.

    Every per-step reduction — model contributions of all leaves, receipt
    counts, and the freshness statistic (age moments or histogram bins) —
    is packed into columns of a single [F, ...] matrix so the whole step
    costs exactly one collective (an ``ordered_psum``: all_gather plus a
    rank-order fold, so the float reduction order is identical across
    backends and process counts). On a scan of thousands of steps the
    collective rendezvous is the dominant cost; fusing ~10 all-reduces
    into 1 is most of the engine's win.
    """
    from repro.core.distributed import _tree_mix, ordered_psum
    cfg = dcfg.pop
    fcfg = cfg.freshness
    axes = ((dcfg.pod_axis, dcfg.data_axis) if dcfg.pod_axis
            else (dcfg.data_axis,))
    reduce_axes = axes if dcfg.cross_pod else (dcfg.data_axis,)

    def step(st, info, batches, key):
        t = st["t"]
        fid = info["fixed_id"]
        m_loc = fid.shape[0]
        deliver = info["exchange"] & (fid >= 0)
        if info.get("active") is not None:
            # churn folds into the delivery mask, so inactive mules vanish
            # from the fused psum payload (model columns, counts, and the
            # freshness statistic alike) — distributed == single-host
            # under any mask by construction
            deliver = deliver & info["active"]
        with jax.named_scope("mule_fresh"):
            ages = t - st["mule_ts"]
            fresh = st["fresh"]
            thr = fresh["threshold"][jnp.maximum(fid, 0)]
            if fcfg.stat == "median":
                warm = fresh["count"][jnp.maximum(fid, 0)] < fcfg.warmup
                fresh_ok = deliver & (warm | (ages <= thr))
            else:
                # legacy semantics preserved from the retired per-step
                # path: meanstd carries no receipt counts, so
                # FreshnessConfig.warmup is ignored — acceptance is the
                # bare threshold test
                fresh_ok = deliver & (ages <= thr)

        # -- fused segment-reduce + ONE all-reduce ---------------------------
        with jax.named_scope("mule_space"):
            onehot = jax.nn.one_hot(jnp.maximum(fid, 0), cfg.n_fixed, axis=0)
            a_loc = onehot * fresh_ok[None, :].astype(jnp.float32)  # [F, m]
            leaves, treedef = jax.tree.flatten(st["mule_models"])
            shapes = [l.shape[1:] for l in leaves]
            sizes = [int(np.prod(s)) if s else 1 for s in shapes]
            flat = jnp.concatenate(
                [l.reshape(m_loc, -1).astype(jnp.float32) for l in leaves]
                + [jnp.ones((m_loc, 1), jnp.float32)], axis=1)
            cols_a = [a_loc @ flat]            # models | counts  [F, D+1]
        with jax.named_scope("mule_fresh"):
            if fcfg.stat == "meanstd":
                cols_a.append(a_loc @ jnp.stack([ages, ages ** 2], axis=1))
            else:
                d_loc = onehot * deliver[None, :].astype(jnp.float32)
                bins = age_bin_onehot(ages, fcfg)              # [M_loc, B]
                cols_a.append(d_loc @ jnp.concatenate(
                    [bins, jnp.ones((m_loc, 1), jnp.float32)], axis=1))
        with jax.named_scope("mule_space"):
            # ordered_psum, not lax.psum: the fold order of this float
            # payload must not depend on the backend, or multi-process runs
            # drift ULPs off the single-process bitwise pins (integer
            # reductions elsewhere are exact and stay raw)
            fused = ordered_psum(jnp.concatenate(cols_a, axis=1),
                                 reduce_axes)

            d_total = sum(sizes)
            part_flat = fused[:, :d_total]
            counts = fused[:, d_total]
            has = (counts > 0).astype(jnp.float32)
            norm = part_flat / jnp.maximum(counts, 1.0)[:, None]
            outs, off = [], 0
            for s, n, l in zip(shapes, sizes, leaves):
                outs.append(norm[:, off:off + n]
                            .reshape((cfg.n_fixed,) + s).astype(l.dtype))
                off += n
            agg = jax.tree.unflatten(treedef, outs)
            gamma = (cfg.gamma / (1.0 + cfg.prox_mu)
                     if cfg.aggregation == "prox" else cfg.gamma)
            fixed_models = _tree_mix(st["fixed_models"], agg, gamma * has)

        # -- freshness threshold update --------------------------------------
        with jax.named_scope("mule_fresh"):
            if fcfg.stat == "median":
                # paper semantics: every *delivered* age is pushed (accepted
                # or not). Mule shards are replicated across pods, so a
                # cross_pod reduce folds n_pods copies into the histogram
                # and counts; quantiles are scale-invariant but warmup
                # counts are not, so both are divided back down (psum of a
                # literal is the axis size, folded at compile time — no
                # extra collective).
                n_rep = (jax.lax.psum(1, dcfg.pod_axis)
                         if dcfg.pod_axis and dcfg.cross_pod else 1)
                step_hist = fused[:, d_total + 1:-1] / n_rep
                step_cnt = fused[:, -1] / n_rep
                fresh = sketch_push_and_update(fresh, step_hist, step_cnt,
                                               fcfg)
            else:
                # legacy deviation: EMA of this step's accepted-age mean/std
                age_sum, age_sq = fused[:, -2], fused[:, -1]
                mean_age = age_sum / jnp.maximum(counts, 1.0)
                var_age = jnp.maximum(
                    age_sq / jnp.maximum(counts, 1.0) - mean_age ** 2, 0.0)
                target = mean_age + fcfg.beta * jnp.sqrt(var_age)
                fresh = {"threshold": jnp.where(
                    counts > 0,
                    (1 - fcfg.alpha) * fresh["threshold"]
                    + fcfg.alpha * target,
                    fresh["threshold"])}

        # -- training + send-back (paper Fig. 2 cycles) ----------------------
        if cfg.mode == "fixed":
            with jax.named_scope("mule_train"):
                keys = jax.random.split(key, cfg.n_fixed)
                trained = jax.vmap(train_fn)(fixed_models, batches["fixed"],
                                             keys)
                fixed_models = _tree_mix(fixed_models, trained, has)

        with jax.named_scope("mule_space"):
            per_mule_fixed = jax.tree.map(
                lambda l: l[jnp.maximum(fid, 0)], fixed_models)
            gm = cfg.gamma * deliver.astype(jnp.float32)
            mule_models = _tree_mix(st["mule_models"], per_mule_fixed, gm)

        if cfg.mode == "mobile":
            with jax.named_scope("mule_train"):
                mb = jax.tree.map(lambda l: _local_block(dcfg, l, m_loc),
                                  batches["mule"])
                keys = _mule_train_keys(dcfg, key, m_loc)
                trained = jax.vmap(train_fn)(mule_models, mb, keys)
                mule_models = _tree_mix(mule_models, trained,
                                        deliver.astype(jnp.float32))

        with jax.named_scope("mule_space"):
            mule_ts = jnp.where(deliver, t, st["mule_ts"])
        return {
            "mule_models": mule_models,
            "fixed_models": fixed_models,
            "mule_ts": mule_ts,
            "fresh": fresh,
            "t": t + 1.0,
        }

    return step
