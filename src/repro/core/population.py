"""Vectorized ML Mule population engine.

The whole device population is simulated as stacked pytrees:
mule models [M, ...], fixed-device models [F, ...]. One ``population_step``
executes the paper's In-House cycles for every concurrent co-location in a
single masked batched update:

fixed-device training (share-aggregate-train-share, Fig. 2a):
  1. mules with a completed exchange deliver snapshots to their fixed device
  2. freshness filter (dynamic threshold) drops stale snapshots
  3. each fixed device folds the dwell-weighted mean of accepted snapshots
     into its model (masked_group_mean — the ``mule_agg`` hot spot)
  4. fixed devices that received anything train one step on local data
  5. mules receive the updated model back and fold it into their own

mobile-device training (share-aggregate-share-train, Fig. 2b):
  steps 1–3 identical (the mule "leaves a record of having visited");
  4'. mules receive the aggregated model back and fold it in
  5'. mules train one step on their own data

The Mule phase is implicit: a mule not co-located simply carries its model
(its timestamp ages, which is what the freshness filter measures).

``make_method_step`` generalizes the step to every mobile-protocol method
the paper compares (``METHODS_MOBILE``): ML Mule above, plus the
decentralized baselines (gossip / oppcl / local-only and the mlmule+gossip
hybrid). All of them share one traceable signature
``(state, info, batches, key) -> state`` so the scan engine
(``repro.scenarios.engine``) can replay any method as a single compiled
program; the 3-step peer-exchange cadence (paper Sec 4.3.1) is a
``lax.cond`` on the step index carried in ``info["t"]``.

Population churn: ``info["active"]`` ([M] bool, optional) marks which mules
are switched on this step. An inactive mule neither trains, delivers,
receives, nor serves as a gossip/oppcl peer — its model, timestamp, and
freshness records are carried bitwise (``apply_activity_mask`` selects old
leaves back in after the dense update). An all-ones mask reproduces the
dense path bitwise: masking enters only as ``& active`` on the delivery
mask and elementwise ``jnp.where`` selects, never as a change to the dense
computation itself.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.core.aggregation import batched_mix, masked_group_mean
from repro.core.freshness import FreshnessConfig, accept_mask, init_freshness, push_and_update

TrainFn = Callable[[Any, Any, jnp.ndarray], Any]   # (params, batch, key) -> params


@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    mode: str = "fixed"            # "fixed" | "mobile" — which side trains
    n_fixed: int = 8
    n_mules: int = 20
    gamma: float = 0.5             # aggregation mixing weight
    freshness: FreshnessConfig = FreshnessConfig()
    agg_backend: str = "ref"
    enc_backend: str = "ref"       # peer-encounter mix: ref | pallas | auto
    aggregation: str = "weighted"  # weighted | prox (FedProx-style damping)
    prox_mu: float = 0.1


def init_population(key, init_model_fn: Callable[[jnp.ndarray], Any],
                    cfg: PopulationConfig) -> Dict[str, Any]:
    km, kf = jax.random.split(key)
    mule_models = jax.vmap(init_model_fn)(jax.random.split(km, cfg.n_mules))
    fixed_models = jax.vmap(init_model_fn)(jax.random.split(kf, cfg.n_fixed))
    return {
        "mule_models": mule_models,
        "fixed_models": fixed_models,
        "mule_ts": jnp.zeros((cfg.n_mules,), jnp.float32),
        "fresh": init_freshness(cfg.n_fixed, cfg.freshness),
        "t": jnp.zeros((), jnp.float32),
    }


def apply_activity_mask(active, new: Any, old: Any) -> Any:
    """Per-leaf select: lane ``m`` takes ``new`` where ``active[m]``.

    ``active`` broadcasts against each leaf's leading (population) axis, so
    inactive lanes carry ``old`` bitwise; an all-ones mask returns ``new``
    bitwise (``jnp.where`` is an elementwise select of already-computed
    values — it never perturbs the dense update). ``active=None`` means no
    churn and returns ``new`` unchanged, so call sites need no guard.
    """
    if active is None:
        return new

    def sel(n, o):
        m = active.reshape(active.shape + (1,) * (n.ndim - active.ndim))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def population_step(state: Dict[str, Any], info: Dict[str, jnp.ndarray],
                    batches: Dict[str, Any], train_fn: TrainFn,
                    cfg: PopulationConfig, key) -> Dict[str, Any]:
    """One simulation time step.

    info:    {"fixed_id": [M] int32 (-1 = corridor), "exchange": [M] bool,
              "active": [M] bool (optional; absent == all active)}
    batches: {"fixed": [F, B, ...], "mule": [M, B, ...]} (per mode; a mode
             only reads the side that trains).

    An inactive mule (``~info["active"]``) delivers nothing, receives
    nothing, and (mobile mode) does not train — every per-mule effect of
    the protocol is already gated on ``deliver``, so folding the mask into
    it covers the whole cycle.

    The step's layers carry ``jax.named_scope`` names (``mule_fresh``,
    ``mule_space``, ``mule_train``), which reach the compiled program's
    ``op_name`` metadata; a profiler trace is split by layer on them.
    """
    t = state["t"]
    fid = info["fixed_id"]
    deliver = info["exchange"] & (fid >= 0)
    if info.get("active") is not None:
        deliver = deliver & info["active"]

    # -- 1–2: deliver + freshness filter ------------------------------------
    with jax.named_scope("mule_fresh"):
        ages = t - state["mule_ts"]
        fresh_ok = accept_mask(state["fresh"], fid, ages,
                               cfg.freshness) & deliver

    # -- 3: dwell-weighted aggregation at fixed devices ----------------------
    with jax.named_scope("mule_space"):
        assign = (jax.nn.one_hot(jnp.maximum(fid, 0), cfg.n_fixed, axis=0)
                  * fresh_ok[None, :].astype(jnp.float32))      # [F, M]
        agg, mass = masked_group_mean(state["mule_models"], assign,
                                      backend=cfg.agg_backend)
        has = (mass > 0).astype(jnp.float32)
        gamma = cfg.gamma / (1.0 + cfg.prox_mu) \
            if cfg.aggregation == "prox" else cfg.gamma
        fixed_models = batched_mix(state["fixed_models"], agg, gamma * has)

    with jax.named_scope("mule_fresh"):
        fresh = push_and_update(state["fresh"], fid, ages, deliver,
                                cfg.freshness)

    # -- 4: training ----------------------------------------------------------
    if cfg.mode == "fixed":
        with jax.named_scope("mule_train"):
            keys = jax.random.split(key, cfg.n_fixed)
            trained = jax.vmap(train_fn)(fixed_models, batches["fixed"], keys)
            fixed_models = batched_mix(fixed_models, trained, has)  # only active devices
    # -- 5: send back to mules ------------------------------------------------
    with jax.named_scope("mule_space"):
        per_mule_fixed = jax.tree.map(lambda l: l[jnp.maximum(fid, 0)],
                                      fixed_models)
        gm = cfg.gamma * deliver.astype(jnp.float32)
        mule_models = batched_mix(state["mule_models"], per_mule_fixed, gm)

    if cfg.mode == "mobile":
        with jax.named_scope("mule_train"):
            keys = jax.random.split(key, cfg.n_mules)
            trained = jax.vmap(train_fn)(mule_models, batches["mule"], keys)
            mule_models = batched_mix(mule_models, trained,
                                      deliver.astype(jnp.float32))

    with jax.named_scope("mule_space"):
        mule_ts = jnp.where(deliver, t, state["mule_ts"])
    return {
        "mule_models": mule_models,
        "fixed_models": fixed_models,
        "mule_ts": mule_ts,
        "fresh": fresh,
        "t": t + 1.0,
    }


# ---------------------------------------------------------------------------
# method dispatch: every mobile-protocol method as one step signature
# ---------------------------------------------------------------------------

# The five methods of the paper's mobile-device experiments (Figs 6-9).
METHODS_MOBILE = ("mlmule", "gossip", "oppcl", "local", "mlmule+gossip")


def make_method_step(method: str, train_fn: TrainFn, cfg: PopulationConfig,
                     area: jnp.ndarray) -> Callable:
    """Build a traceable one-step update for any ``METHODS_MOBILE`` method.

    Thin wrapper: the method's semantics live in the one
    ``repro.core.method_program.METHOD_PROGRAMS`` table (cadences, key
    discipline, churn handling — see that module for the contract and the
    recipe for adding a method), and ``compile_step`` lowers the program to
    the single-host scan step. The returned function has the uniform
    signature ``step(state, info, batches, key) -> state`` where ``info``
    extends the ``population_step`` contract with ``"pos"`` ([M, 2] mule
    positions) and ``"t"`` (scalar int32 step index); ``area`` is the
    per-mule area vector the peer-encounter methods need (areas are
    isolated). Bitwise-pinned by the parity tests against
    ``run_population_loop``.
    """
    # deferred: method_program builds on repro.core + repro.baselines
    from repro.core.method_program import compile_step, get_program
    return compile_step(get_program(method), train_fn, cfg, area)


# ---------------------------------------------------------------------------
# evaluation helpers
# ---------------------------------------------------------------------------


def eval_population(models: Any, eval_fn: Callable[[Any, Any], jnp.ndarray],
                    test_data: Any) -> jnp.ndarray:
    """models: stacked [P, ...]; test_data: stacked [P, N, ...] -> metric [P]."""
    return jax.vmap(eval_fn)(models, test_data)
