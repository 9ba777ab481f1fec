"""Scenario-engine microbenchmarks: driver overhead and sweep throughput.

``run()`` — per-step Python-loop driver vs the compiled ``lax.scan`` engine
on the same 500-step, 20-mule workload. The loop driver is the harness's
former hot path — one jitted ``population_step`` dispatch (plus batch
sampling and key splits) per time step; it survives as
``repro.scenarios.run_population_loop``, the parity reference. The engine
compiles the whole replay into one XLA program; the gap is almost pure
Python/jit dispatch overhead.

``run_sweep_bench()`` — the multi-seed sweep path: sequential
``run_population`` calls that retrace per call (the pre-cache behavior,
reproduced by clearing the jit cache between calls) vs ONE vmapped
compiled program over all seeds (``run_sweep``) hitting the cache.
Also asserts the jit cache's contract: a second same-shape
``run_population`` call performs zero retraces. Results land in
``BENCH_sweep.json`` so the perf trajectory is tracked PR over PR.

``run_distributed_bench()`` — the mule-sharded path: the per-step
``run_population_distributed_loop`` driver (one jitted shard_map dispatch
per time step) vs the scan-based ``run_population_distributed`` (ONE
program, both freshness statistics), on a forced-host-device mesh. Also asserts zero
retraces on the warm call and that a vmapped distributed sweep is
bitwise-equal per lane to sequential distributed runs. Results land in
``BENCH_distributed.json``. Needs ≥ 8 devices: invoked without them, it
re-execs itself in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``run_churn_bench()`` — masked (churn) vs dense replay on the same schedule
shape: same compiled program (the mask is data — zero retraces), warm
overhead pinned <= 10% and tracked in ``BENCH_churn.json``.

``run_encounter_bench()`` — the peer-encounter mix: tiled
``encounter_mix`` kernel vs the retired dense path ([M, M] encounter
matrix + per-leaf ``masked_group_mean``; the tiled warm step must win),
plus ring-sharded vs single-host warm gossip replays on the forced
host-device mesh. Results land in ``BENCH_encounter.json``.

``run_migration_bench()`` — long-trace hop-prune decay: the exact host
mirror of the ring's pruning predicate on a persistent-relocation area
trace, with build-time bucketing only vs the drift-triggered mid-run
re-bucketing rule; asserts the re-bucketed prune rate holds into the
final quartile and merges retention telemetry into
``BENCH_encounter.json`` (run after ``--encounter``).

``run_donation_bench()`` — compile-time memory deltas of donating the
state pytree to the cached replay (``run_population(..., donate=True)``):
XLA aliases the state buffers into the outputs, so steady-state peak drops
by the full population size.

``run_roofline_bench()`` — the autotuner (``repro.launch.autotune``): the
scan-aware HLO analysis over the compiled engine step per (method × M ×
mesh) plus the measured kernel block-size sweeps, producing the tuning
cache ``BENCH_roofline.json`` that ``encounter_mix``/``mule_agg`` read
their tile sizes from. Needs ≥ 8 devices for the mesh rows; re-execs
itself with forced host devices like the distributed bench.

``run_scale_bench()`` — the population-scale curve (``--scale``): the
streamed engine (``run_population_streamed`` + the procedural
``commuter_stream`` generator, O(chunk·M) schedule memory) vs the classic
materialized ``[T, M]`` replay, per M up to 10^5, each (M, mode) in its own
subprocess so ``ru_maxrss`` is honest per-engine peak-RSS telemetry.
Cross-process sha256 digests of the final mule models pin streamed ==
materialized bitwise at every M, and a half-horizon replay pins the chunk
program as T-free (zero retraces). Results land in ``BENCH_scale.json``.

Every artifact is a gated ratchet: ``--gate-baseline DIR`` compares
whatever artifacts this invocation produced against the committed copies
in DIR via ``benchmarks.bench_gate`` and exits non-zero on a regression
(the CI slow lane snapshots the checkout's artifacts and passes that
directory here — see benchmarks/README.md).

  PYTHONPATH=src python -m benchmarks.engine_micro               # all
  PYTHONPATH=src python -m benchmarks.engine_micro --sweep       # sweep only
  PYTHONPATH=src python -m benchmarks.engine_micro --distributed # dist only
  PYTHONPATH=src python -m benchmarks.engine_micro --churn       # churn only
  PYTHONPATH=src python -m benchmarks.engine_micro --roofline    # autotune
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.mule_cnn import CNNConfig
from repro.core import PopulationConfig, init_population
from repro.core.freshness import FreshnessConfig
from repro.models.cnn import cnn_forward, init_cnn, xent_loss
from repro.scenarios import (jit_cache_clear, jit_cache_stats,
                             run_population, run_population_distributed,
                             run_population_loop, run_sweep,
                             run_sweep_distributed, stack_colocations,
                             stack_trees, walk_colocation)

_DEFAULT_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_sweep.json")
_DEFAULT_DIST_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BENCH_distributed.json")
_DEFAULT_CHURN_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "BENCH_churn.json")
_DEFAULT_ENC_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_encounter.json")
_DEFAULT_ROOF_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BENCH_roofline.json")
_DEFAULT_SCALE_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "BENCH_scale.json")


def _setup(n_fixed=8, n_mules=20, steps=500, batch=2, image=4, seed=0):
    # deliberately tiny CNN: the benchmark isolates driver overhead (Python
    # dispatch per step), so per-step FLOPs are kept well below dispatch cost
    mc = CNNConfig(image_size=image, conv_features=(2, 2), hidden=8,
                   n_classes=10)
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (n_fixed, 64, image, image, 3))
    Y = jax.random.randint(key, (n_fixed, 64), 0, 10)

    def train_fn(params, b, k):
        xb, yb = b
        g = jax.grad(lambda p: xent_loss(cnn_forward(p, xb), yb))(params)
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

    def batch_fn(k, t):
        idx = jax.random.randint(k, (n_fixed, batch), 0, X.shape[1])
        return {"fixed": (jnp.take_along_axis(
                              X, idx[:, :, None, None, None], 1),
                          jnp.take_along_axis(Y, idx, 1)), "mule": None}

    pcfg = PopulationConfig(mode="fixed", n_fixed=n_fixed, n_mules=n_mules)
    pop = init_population(jax.random.PRNGKey(seed + 1),
                          lambda k: init_cnn(k, mc), pcfg)
    co = walk_colocation(seed, n_mules, steps)
    return pop, co, batch_fn, train_fn, pcfg


def _block(tree):
    jax.block_until_ready(jax.tree.leaves(tree)[0])


def run(steps: int = 500, n_mules: int = 20):
    pop, co, batch_fn, train_fn, pcfg = _setup(n_mules=n_mules, steps=steps)
    key = jax.random.PRNGKey(7)

    # warm up both drivers (compile), then time one full replay each
    short = {k: (v[:3] if getattr(v, "ndim", 0) > 1 and v.shape[0] == steps
                 else v) for k, v in co.items()}
    _block(run_population_loop(pop, short, batch_fn, train_fn, pcfg, key)[0])
    t0 = time.perf_counter()
    out, _ = run_population_loop(pop, co, batch_fn, train_fn, pcfg, key)
    _block(out)
    loop_s = time.perf_counter() - t0

    # first call traces + compiles and fills the cache; the timed second
    # call is a pure cache hit measuring steady-state execution
    jit_cache_clear()
    _block(run_population(pop, co, batch_fn, train_fn, pcfg, key)[0])
    t0 = time.perf_counter()
    _block(run_population(pop, co, batch_fn, train_fn, pcfg, key)[0])
    scan_s = time.perf_counter() - t0
    assert jit_cache_stats()["traces"] == 1, "cached engine retraced"

    rows = [
        (f"engine.loop.T{steps}", loop_s * 1e6 / steps, "us/step"),
        (f"engine.scan.T{steps}", scan_s * 1e6 / steps, "us/step"),
        (f"engine.speedup.T{steps}", loop_s / scan_s, "x (loop/scan)"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.1f},{derived}")
    return rows


def run_sweep_bench(n_seeds: int = 8, steps: int = 300, n_mules: int = 20,
                    out_path: str = _DEFAULT_OUT):
    """8-seed mlmule sweep: sequential retraced vs one vmapped program."""
    setups = [_setup(n_mules=n_mules, steps=steps, seed=s)
              for s in range(n_seeds)]
    _, _, batch_fn, train_fn, pcfg = setups[0]
    keys = [jax.random.PRNGKey(1000 + s) for s in range(n_seeds)]

    # -- sequential, retraced: the pre-cache engine paid one trace+compile
    # per (seed, method) cell; clearing the cache reproduces that cost
    t0 = time.perf_counter()
    for (pop, co, _, _, _), key in zip(setups, keys):
        jit_cache_clear()
        _block(run_population(pop, co, batch_fn, train_fn, pcfg, key)[0])
    seq_s = time.perf_counter() - t0

    # -- one vmapped compiled program over all seeds (cold: includes its
    # single trace+compile; warm: pure execution)
    states = stack_trees([s[0] for s in setups])
    cos = stack_colocations([s[1] for s in setups])
    kstack = stack_trees(keys)
    jit_cache_clear()
    t0 = time.perf_counter()
    _block(run_sweep(states, cos, batch_fn, train_fn, pcfg, kstack)[0])
    vmap_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _block(run_sweep(states, cos, batch_fn, train_fn, pcfg, kstack)[0])
    vmap_warm_s = time.perf_counter() - t0

    # -- cache contract: a second same-shape run_population call must not
    # retrace (this is what made the sequential path slow to begin with)
    jit_cache_clear()
    pop, co = setups[0][0], setups[0][1]
    _block(run_population(pop, co, batch_fn, train_fn, pcfg, keys[0])[0])
    before = jit_cache_stats()["traces"]
    _block(run_population(pop, co, batch_fn, train_fn, pcfg, keys[1])[0])
    retraces = jit_cache_stats()["traces"] - before
    assert retraces == 0, "second same-shape run_population call retraced"

    speedup = seq_s / vmap_cold_s
    rows = [
        (f"sweep.sequential_retraced.S{n_seeds}.T{steps}", seq_s, "s total"),
        (f"sweep.vmapped_cold.S{n_seeds}.T{steps}", vmap_cold_s, "s total"),
        (f"sweep.vmapped_warm.S{n_seeds}.T{steps}", vmap_warm_s, "s total"),
        (f"sweep.speedup.S{n_seeds}.T{steps}", speedup,
         "x (sequential/vmapped-cold)"),
        (f"sweep.retraces_second_call", retraces, "count"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.3f},{derived}")

    payload = {
        "bench": "engine_micro.run_sweep_bench",
        "config": {"n_seeds": n_seeds, "steps": steps, "n_mules": n_mules,
                   "method": "mlmule", "backend": jax.default_backend()},
        "sequential_retraced_s": round(seq_s, 4),
        "vmapped_cold_s": round(vmap_cold_s, 4),
        "vmapped_warm_s": round(vmap_warm_s, 4),
        "speedup_vs_sequential": round(speedup, 2),
        "retraces_second_call": int(retraces),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return rows


def run_donation_bench(steps: int = 300, n_mules: int = 20):
    """Compile-time memory effect of donating the replay's state buffers."""
    from repro.scenarios.engine import (dense_colocation,
                                        get_compiled_chunk_replay)

    pop, co, batch_fn, train_fn, pcfg = _setup(n_mules=n_mules, steps=steps)
    key = jax.random.PRNGKey(7)
    gen = dense_colocation(co)
    args = (pop, jnp.zeros((n_mules,), jnp.int32), jnp.asarray(0, jnp.int32),
            gen.arrays(), None, None, key)
    rows = []
    for donate in (False, True):
        fn = get_compiled_chunk_replay(
            pop, gen, gen.arrays(), batch_fn, None, key, train_fn, pcfg,
            method="mlmule", eval_every=None, eval_fn=None, chunk_len=steps,
            donate=donate)
        try:
            ma = fn.lower(*args).compile().memory_analysis()
            alias = int(ma.alias_size_in_bytes)
            peak = (int(ma.argument_size_in_bytes)
                    + int(ma.output_size_in_bytes)
                    + int(ma.temp_size_in_bytes) - alias)
        except Exception:                      # backend without the analysis
            alias, peak = -1, -1
        tag = "donated" if donate else "plain"
        rows.append((f"engine.memory.{tag}.T{steps}", peak, "bytes peak"))
        rows.append((f"engine.memory.{tag}.alias", alias, "bytes aliased"))
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")
    return rows


def run_churn_bench(steps: int = 500, n_mules: int = 20, reps: int = 5,
                    out_path: str = _DEFAULT_CHURN_OUT):
    """Masked vs dense replay on the same schedule shape.

    The activity mask is *data*, not a static: a churned run must reuse the
    dense run's compiled program (zero retraces) and cost essentially the
    same wall clock — the mask only adds elementwise selects to a scan
    dominated by training math. Asserts the warm-run overhead stays <= 10%
    (median of ``reps``) and records it in ``BENCH_churn.json``.
    """
    from repro.mobility import markov_churn_mask

    pop, co, batch_fn, train_fn, pcfg = _setup(n_mules=n_mules, steps=steps)
    key = jax.random.PRNGKey(7)
    co_churn = dict(co)
    co_churn["active"] = markov_churn_mask(11, steps, n_mules,
                                           p_leave=0.05, p_join=0.15)
    active_frac = float(co_churn["active"].mean())

    jit_cache_clear()
    _block(run_population(pop, co, batch_fn, train_fn, pcfg, key)[0])
    before = jit_cache_stats()["traces"]
    _block(run_population(pop, co_churn, batch_fn, train_fn, pcfg, key)[0])
    retraces = jit_cache_stats()["traces"] - before
    assert retraces == 0, "churned same-shape run retraced the dense program"

    def timed(schedule):
        t0 = time.perf_counter()
        _block(run_population(pop, schedule, batch_fn, train_fn, pcfg,
                              key)[0])
        return time.perf_counter() - t0

    dense_s = [timed(co) for _ in range(reps)]
    churn_s = [timed(co_churn) for _ in range(reps)]
    dense_med = sorted(dense_s)[reps // 2]
    churn_med = sorted(churn_s)[reps // 2]
    overhead = churn_med / dense_med - 1.0
    assert overhead <= 0.10, \
        f"masked scan overhead {overhead:.1%} exceeds the 10% budget"

    rows = [
        (f"churn.dense_warm.T{steps}", dense_med, "s (median)"),
        (f"churn.masked_warm.T{steps}", churn_med, "s (median)"),
        (f"churn.overhead.T{steps}", overhead * 100.0, "% (masked/dense-1)"),
        ("churn.retraces_masked_call", retraces, "count"),
        ("churn.active_frac", active_frac, "mean mask"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")

    payload = {
        "bench": "engine_micro.run_churn_bench",
        "config": {"steps": steps, "n_mules": n_mules, "reps": reps,
                   "method": "mlmule", "backend": jax.default_backend()},
        "dense_warm_s": round(dense_med, 4),
        "masked_warm_s": round(churn_med, 4),
        "overhead_pct": round(overhead * 100.0, 2),
        "retraces_masked_call": int(retraces),
        "active_frac": round(active_frac, 4),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return rows


def _require_host_cpu(what: str) -> None:
    """The benches that spawn children run them on forced host CPU devices.

    A child that needed an accelerator would find it held by this process
    (one process per chip), so on anything but the CPU backend this stops
    before any child starts and names the devices it found.
    """
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{what} runs child processes on forced host CPU devices, but "
            f"this process holds {jax.devices()}; run it with "
            "JAX_PLATFORMS=cpu")


def _respawn_with_devices(n_devices: int, out_path: str,
                          flag: str = "--distributed",
                          out_flag: str = "--out-distributed") -> None:
    """Re-exec a device-hungry bench in a child with N forced host devices."""
    _require_host_cpu(f"engine_micro {flag}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}"
                        ).strip()
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep +
                         env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    env["_REPRO_DIST_BENCH_CHILD"] = "1"   # forbid a second respawn
    subprocess.run([sys.executable, "-m", "benchmarks.engine_micro",
                    flag, out_flag, out_path],
                   env=env, cwd=root, check=True)


def run_encounter_bench(n_mules: int = 8192, reps: int = 5,
                        n_devices: int = 8, ring_mules: int = 8192,
                        ring_steps: int = 9, ring_areas: int = 8,
                        out_path: str = _DEFAULT_ENC_OUT):
    """Peer-encounter mix: tiled kernel vs the retired dense path, plus the
    locality-aware ring (pruned AND unpruned) vs a single-host warm gossip
    replay at the same M=8192.

    The dense path builds the full [M, M] encounter matrix, normalizes it,
    and runs one ``masked_group_mean`` matmul *per model leaf* — O(M^2)
    reads per leaf on top of the O(M^2 * D) MACs. The fused op
    (``repro.kernels.encounter_mix``) flattens the model pytree once and
    computes distance test + row-normalized mix tile by tile, so the
    [M, M] matrix and the per-leaf passes never exist. Asserts the fused
    warm step beats the dense warm step and records both in
    ``BENCH_encounter.json``.

    The ring rows replay one gossip workload three ways: single-host, the
    bucket-sharded pruned ring (``DistributedConfig.ring_prune=True``, the
    engine default), and the same ring with pruning off (every hop streams
    every block — the pre-locality behaviour). Mules carry ``ring_areas``
    balanced random areas and are ordered by ``bucket_mule_order`` before
    sharding, so the area-bitmask predicate can prove remote hops empty;
    the recorded telemetry (hops executed/pruned per exchange step, payload
    bytes per exchange, bucket-locality fraction) makes a future regression
    diagnosable. ``ring_vs_host`` — the pruned ring's speedup — is gated by
    ``bench_gate`` alongside the tiled-kernel headline; both ring variants
    must agree bitwise (asserted here, and pinned with scenario coverage in
    ``tests/test_ring_exchange.py``). Needs ``n_devices``; without them the
    bench re-execs itself like ``run_distributed_bench``.
    """
    import dataclasses

    import numpy as np
    from repro.baselines.gossip import (area_bit_collision_rate,
                                        encounter_matrix,
                                        flatten_population, ring_hop_mask,
                                        unflatten_population)
    from repro.core.aggregation import masked_group_mean
    from repro.core.distributed import (DistributedConfig,
                                        bucket_locality_fraction,
                                        bucket_mule_order,
                                        reorder_colocation,
                                        reorder_mule_state,
                                        to_distributed_state)
    from repro.kernels.encounter_mix import encounter_mix

    out_path = os.path.abspath(out_path)
    if jax.device_count() < n_devices:
        if os.environ.get("_REPRO_DIST_BENCH_CHILD"):
            raise RuntimeError(
                f"need >= {n_devices} devices but forcing host devices "
                f"yielded {jax.device_count()} on backend "
                f"{jax.default_backend()!r}")
        _respawn_with_devices(n_devices, out_path, flag="--encounter",
                              out_flag="--out-encounter")
        with open(out_path) as f:
            payload = json.load(f)
        return [(k, v, "from respawned child") for k, v in payload.items()
                if isinstance(v, (int, float))]

    # -- tiled kernel vs dense [M, M] + per-leaf group mean ------------------
    # the paper's mobile regime at ROADMAP scale: a large population of
    # tiny on-device models (M >> D), a pytree of many small leaves —
    # exactly where the retired path pays one [M, M] normalization read
    # per leaf and the [M, M] matrix itself dominates the traffic
    m = n_mules
    leaf_shapes = ([(8,)] * 4 + [(16,)] * 4 + [(4, 4)] * 4
                   + [(6, 16)] * 2 + [(16, 4)] * 2)      # 16 leaves, D=480
    models = {f"l{i}": jax.random.normal(jax.random.PRNGKey(i), (m,) + s)
              for i, s in enumerate(leaf_shapes)}
    d_total = sum(int(np.prod(l.shape[1:]))
                  for l in jax.tree.leaves(models))
    ks = jax.random.split(jax.random.PRNGKey(99), 3)
    pos = jax.random.uniform(ks[0], (m, 2))
    area = jax.random.randint(ks[1], (m,), 0, 2)
    active = jax.random.uniform(ks[2], (m,)) < 0.9
    radius = 0.1

    @jax.jit
    def dense_mix(models, pos, area, active):
        enc = encounter_matrix(pos, area, radius, active).astype(jnp.float32)
        return masked_group_mean(models, enc)

    @jax.jit
    def fused_mix(models, pos, area, active):
        flat, spec = flatten_population(models)
        mixed, mass = encounter_mix(pos, area, active, flat, radius=radius,
                                    backend="pallas", block_m=512)
        return unflatten_population(mixed, spec), mass

    def timed(fn):
        _block(fn(models, pos, area, active)[0])       # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            _block(fn(models, pos, area, active)[0])
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[reps // 2]

    dense_s = timed(dense_mix)
    fused_s = timed(fused_mix)
    assert fused_s < dense_s, \
        f"tiled encounter_mix ({fused_s:.3f}s) lost to the dense path " \
        f"({dense_s:.3f}s)"

    # -- locality-aware ring vs single-host warm gossip replay ---------------
    # same population scale as the kernel half: the regime the ROADMAP item
    # names, where a ring hop moves a [M/n, D] block and locality decides
    # whether it moves at all. Balanced random areas, bucket-ordered before
    # sharding, so each shard is (nearly) one spatial bucket.
    mesh = jax.make_mesh((1, n_devices), ("pod", "data"))
    rm, rt = ring_mules, ring_steps
    rd = 8                                            # per-mule model dim
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    r_area = np.asarray(jax.random.permutation(
        ks[0], np.arange(rm) % ring_areas)).astype(np.int32)
    co_ring = {
        "fixed_id": np.full((rt, rm), -1, np.int32),  # peer exchange only
        "exchange": np.zeros((rt, rm), bool),
        "pos": np.asarray(jax.random.uniform(ks[1], (rt, rm, 2)),
                          np.float32),
        "area": r_area,
    }
    X = jax.random.normal(jax.random.PRNGKey(50), (rm, 12, rd))
    Y = jax.random.normal(jax.random.PRNGKey(60), (rm, 12))

    def train_fn(params, batch, key):
        xb, yb = batch
        g = jax.grad(lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(params)
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

    def batch_fn(key, t):
        idx = jax.random.randint(key, (rm, 4), 0, X.shape[1])
        return {"fixed": None,
                "mule": (jnp.take_along_axis(X, idx[:, :, None], 1),
                         jnp.take_along_axis(Y, idx, 1))}

    pcfg = PopulationConfig(mode="mobile", n_fixed=8, n_mules=rm)
    pop = init_population(jax.random.PRNGKey(1),
                          lambda k: {"w": jax.random.normal(k, (rd,))}, pcfg)

    # bucket sharding: order mules by area at colocation build time (state
    # rows follow their columns); migrate_mules is the mid-run re-bucketing
    # primitive this bench doesn't need (areas are static here)
    order = bucket_mule_order(r_area)
    co_ring = reorder_colocation(co_ring, order)
    pop = reorder_mule_state(pop, order)
    key = jax.random.PRNGKey(7)

    def warm(fn):
        out = fn()[0]
        _block(out)
        t0 = time.perf_counter()
        _block(fn()[0])
        return time.perf_counter() - t0, out

    host_s, host_out = warm(lambda: run_population(
        pop, co_ring, batch_fn, train_fn, pcfg, key, method="gossip"))
    dcfg = DistributedConfig(pop=pcfg)                  # ring_prune=True
    dstate = to_distributed_state(pop, dcfg)
    ring_s, ring_out = warm(lambda: run_population_distributed(
        dstate, co_ring, batch_fn, train_fn, dcfg, mesh, key,
        method="gossip"))
    dcfg_u = dataclasses.replace(dcfg, ring_prune=False)
    unpruned_s, unpruned_out = warm(lambda: run_population_distributed(
        to_distributed_state(pop, dcfg_u), co_ring, batch_fn, train_fn,
        dcfg_u, mesh, key, method="gossip"))
    for a, b in zip(jax.tree.leaves(ring_out["mule_models"]),
                    jax.tree.leaves(unpruned_out["mule_models"])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "pruned and unpruned rings disagree"
    del host_out

    # -- ring telemetry (the host-side mirror of the in-ring predicate) ------
    n_shards = int(mesh.shape["data"])
    m_loc = rm // n_shards
    need = np.asarray(ring_hop_mask(co_ring["area"], None, n_shards))
    hops_executed = int(need.sum())
    hops_pruned = n_shards - hops_executed
    # per executed remote hop every shard sends its (pos f32[2] + area i32 +
    # active bool + flat f32[D]) block; the predicate itself costs one
    # [n, 32] f32 psum per exchange step
    payload_bytes = (n_shards * max(hops_executed - 1, 0)
                     * m_loc * (8 + 4 + 1 + 4 * rd)
                     + n_shards * n_shards * 32 * 4)
    locality = bucket_locality_fraction(co_ring["area"], n_shards)
    # effective predicate width this run resolves to (ring_bits=0 -> auto)
    ring_bits = 64 if int(co_ring["area"].max()) >= 32 else 32
    collision = area_bit_collision_rate(co_ring["area"], n_bits=ring_bits)

    rows = [
        (f"encounter.dense_warm.M{m}", dense_s, "s (median)"),
        (f"encounter.tiled_warm.M{m}", fused_s, "s (median)"),
        (f"encounter.speedup.M{m}", dense_s / fused_s, "x (dense/tiled)"),
        (f"encounter.host_gossip_warm.M{rm}.T{rt}", host_s, "s total"),
        (f"encounter.ring_gossip_warm.M{rm}.T{rt}", ring_s,
         "s total (pruned)"),
        (f"encounter.ring_unpruned_warm.M{rm}.T{rt}", unpruned_s,
         "s total"),
        (f"encounter.ring_vs_host.M{rm}.T{rt}", host_s / ring_s,
         "x (host/pruned ring, gated)"),
        (f"encounter.ring_vs_host_unpruned.M{rm}.T{rt}",
         host_s / unpruned_s, "x (host/unpruned ring)"),
        (f"encounter.hops.n{n_shards}", hops_executed,
         f"executed per exchange step ({hops_pruned} pruned)"),
        (f"encounter.payload_bytes", payload_bytes, "B per exchange step"),
        (f"encounter.bucket_locality", locality,
         "fraction of same-area pairs shard-local"),
        (f"encounter.area_bits_collision.b{ring_bits}", collision,
         "fraction of areas sharing a summary bit"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")

    payload = {
        "bench": "engine_micro.run_encounter_bench",
        "config": {"n_mules": m, "d_total": int(d_total),
                   "n_leaves": len(jax.tree.leaves(models)),
                   "radius": radius, "reps": reps,
                   "ring_mules": rm, "ring_steps": rt,
                   "ring_areas": ring_areas, "ring_model_d": rd,
                   "mesh": dict(mesh.shape),
                   "backend": jax.default_backend()},
        "dense_warm_s": round(dense_s, 4),
        "tiled_warm_s": round(fused_s, 4),
        "speedup_tiled_vs_dense": round(dense_s / fused_s, 2),
        "host_gossip_warm_s": round(host_s, 4),
        "ring_gossip_warm_s": round(ring_s, 4),
        "ring_vs_host": round(host_s / ring_s, 2),
        "ring_unpruned_warm_s": round(unpruned_s, 4),
        "ring_vs_host_unpruned": round(host_s / unpruned_s, 2),
        "hops_executed": hops_executed,
        "hops_pruned": hops_pruned,
        "payload_bytes_per_exchange": float(payload_bytes),
        "bucket_locality_fraction": round(locality, 4),
        "area_bits_collision_rate": round(collision, 4),
    }
    # the long-trace migration bench merges its re-bucketing telemetry into
    # this same artifact; keep those keys when re-running only this half
    try:
        with open(out_path) as f:
            prior = json.load(f)
        payload.update({k: prior[k] for k in _MIGRATION_KEYS if k in prior})
    except (OSError, ValueError):
        pass
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return rows


_MIGRATION_KEYS = (
    "rebucket_every", "rebucket_threshold", "rebucket_checks",
    "rebucket_swaps", "prune_rate_q1_on", "prune_rate_q4_on",
    "prune_rate_q1_off", "prune_rate_q4_off", "rebucket_prune_retention",
)


def run_migration_bench(n_mules: int = 512, n_steps: int = 4096,
                        n_shards: int = 16, rebucket_every: int = 64,
                        threshold: float = 0.001,
                        out_path: str = _DEFAULT_ENC_OUT):
    """Long-trace migration: hop-prune rate over time, re-bucketing on/off.

    Replays a persistent-relocation area trace (``2 * n_shards`` cities so
    bucketing has pruning headroom; 1-in-8 mules permanently moves to a
    random other city at a random step — the paper's rare inter-area
    traveler made permanent, the regime where build-time bucketing decays
    but re-bucketing recovers; round-trip travel visits never prune at any
    cadence because ~a quarter of the population is instantaneously away
    from its bucket) through the *exact* host-side mirror of the ring's
    pruning predicate (``ring_hop_mask`` semantics, vectorized over steps,
    64-bit masks since 32 areas overflow 32 bits) under two shard layouts:

    - **off** — the PR-7 behavior: mules bucket-ordered once at build time;
      as the population migrates the shard/area alignment decays and the
      prune rate drifts toward zero (every hop executed);
    - **on** — the drift-check + argsort swap rule the engine drivers run
      (same cadence, same threshold, same stable re-sort), applied at every
      ``rebucket_every`` boundary.

    Telemetry is deterministic (no timing): per-quartile mean prune rates
    for both layouts, swap/check counts, and the retention ratio
    ``prune_rate_q4_on / prune_rate_q1_on`` — gated by ``bench_gate`` so a
    future change that lets the decay back in fails the lane. Keys merge
    into ``BENCH_encounter.json`` next to the ring-vs-host rows (run this
    after ``--encounter``, which rewrites the file).
    """
    import numpy as np
    from repro.core.distributed import bucket_mule_order

    out_path = os.path.abspath(out_path)
    n_areas = 2 * n_shards
    rng = np.random.default_rng(0)
    home = np.repeat(np.arange(n_areas), n_mules // n_areas).astype(np.int32)
    area_t = np.broadcast_to(home, (n_steps, n_mules)).copy()   # [T, M]
    for m in rng.choice(n_mules, n_mules // 8, replace=False):
        t_move = int(rng.integers(rebucket_every // 2, n_steps))
        area_t[t_move:, m] = (area_t[t_move - 1, m]
                              + int(rng.integers(1, n_areas))) % n_areas
    n_bits = 64 if int(area_t.max()) >= 32 else 32

    def prune_rates(area_rows):
        """[T, M] bucketed area rows -> [T] prune rate, hops_needed math."""
        t_len, m = area_rows.shape
        blocks = area_rows.reshape(t_len, n_shards, m // n_shards)
        hit = blocks[..., None] % n_bits == np.arange(n_bits)
        bits = hit.any(axis=2)                           # [T, S, n_bits]
        need = np.stack([(bits & np.roll(bits, s, axis=1)).any(axis=(1, 2))
                         for s in range(n_shards)], axis=1)
        return (n_shards - need.sum(axis=1)) / (n_shards - 1)

    order0 = bucket_mule_order(area_t)
    off = prune_rates(area_t[:, order0])

    # the driver's rule, replayed on the host: drift check at every
    # rebucket_every boundary, stable re-sort + re-baseline past threshold
    on = np.empty(n_steps)
    order = order0.copy()
    bucket_area = area_t[0][order]
    checks = swaps = 0
    for t0 in range(0, n_steps, rebucket_every):
        w = slice(t0, min(t0 + rebucket_every, n_steps))
        on[w] = prune_rates(area_t[w][:, order])
        t_end = w.stop
        if t_end < n_steps:
            checks += 1
            area_now = area_t[t_end - 1][order]
            if (area_now != bucket_area).mean() > threshold:
                step = np.argsort(area_now, kind="stable")
                if not np.array_equal(step, np.arange(n_mules)):
                    order = order[step]
                    swaps += 1
                bucket_area = area_now[step]

    def quartiles(x):
        return [float(q.mean()) for q in np.array_split(x, 4)]

    q_on, q_off = quartiles(on), quartiles(off)
    retention = q_on[3] / q_on[0] if q_on[0] else 1.0
    rows = [
        ("migration.prune_rate_q1.on", q_on[0], "first-quartile mean"),
        ("migration.prune_rate_q4.on", q_on[3],
         f"final-quartile mean ({swaps} swaps / {checks} checks)"),
        ("migration.prune_rate_q1.off", q_off[0], "first-quartile mean"),
        ("migration.prune_rate_q4.off", q_off[3],
         "final-quartile mean (build-time bucketing only)"),
        ("migration.retention.on", retention, "q4/q1, gated"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
    assert q_on[3] >= 0.9 * q_on[0], \
        f"re-bucketing failed to hold the prune rate: q1={q_on[0]:.3f} " \
        f"q4={q_on[3]:.3f}"

    with open(out_path) as f:
        payload = json.load(f)
    payload["config"]["migration"] = {
        "n_mules": n_mules, "n_steps": n_steps, "n_shards": n_shards,
        "n_areas": n_areas, "n_bits": n_bits,
        "scenario": "persistent-relocation [T, M] area trace"}
    payload.update({
        "rebucket_every": rebucket_every,
        "rebucket_threshold": threshold,
        "rebucket_checks": checks,
        "rebucket_swaps": swaps,
        "prune_rate_q1_on": round(q_on[0], 4),
        "prune_rate_q4_on": round(q_on[3], 4),
        "prune_rate_q1_off": round(q_off[0], 4),
        "prune_rate_q4_off": round(q_off[3], 4),
        "rebucket_prune_retention": round(retention, 4),
    })
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return rows


def run_roofline_bench(n_devices: int = 8, out_path: str = _DEFAULT_ROOF_OUT,
                       reps: int = 3):
    """Roofline autotune sweep -> the ``BENCH_roofline.json`` tuning cache.

    Runs ``repro.launch.autotune.run_roofline``: the compiled engine step
    is decomposed per (method × M) on the single-host engine and per
    method on every candidate (pod, data) mesh shape over the forced
    devices — the rows ``suggest_mesh_shape`` ranks when
    ``run_population_distributed(mesh=None)`` asks for a shape — and every
    feasible ``encounter_mix``/``mule_agg`` block-size candidate is
    measured on the interpret path; the argmin selections land in the
    cache the kernel wrappers read. The headline
    (``tuned_speedup_vs_default``) is gated by ``bench_gate`` like every
    other artifact. Needs ``n_devices`` for the mesh rows; re-execs itself
    with forced host devices otherwise.
    """
    from repro.launch.autotune import run_roofline
    from repro.launch.mesh import make_mule_mesh

    out_path = os.path.abspath(out_path)
    if jax.device_count() < n_devices:
        if os.environ.get("_REPRO_DIST_BENCH_CHILD"):
            raise RuntimeError(
                f"need >= {n_devices} devices but forcing host devices "
                f"yielded {jax.device_count()} on backend "
                f"{jax.default_backend()!r}")
        _respawn_with_devices(n_devices, out_path, flag="--roofline",
                              out_flag="--out-roofline")
        with open(out_path) as f:
            payload = json.load(f)
    else:
        shapes = [(p, n_devices // p) for p in (1, 2, 4)
                  if n_devices % p == 0]
        meshes = [make_mule_mesh(p, d) for p, d in shapes]
        payload = run_roofline(out_path, reps=reps, meshes=meshes)
        print(f"wrote {out_path}")

    rows = []
    for r in payload["roofline"]:
        rows.append((f"roofline.{r['method']}.M{r['n_mules']}"
                     f".mesh{r['mesh']}",
                     r["t_memory_us_per_step"],
                     f"us/step memory term, dominant={r['dominant']}"))
    for e in payload["tuned"]["mule_agg"]:
        rows.append((f"tune.mule_agg.d{e['d']}", e["block_d"],
                     f"block_d ({e['speedup_vs_default']}x vs default)"))
    for e in payload["tuned"]["encounter_mix"]:
        rows.append((f"tune.encounter.m{e['m']}.d{e['d']}",
                     e["block_m"] * 10000 + e["block_d"],
                     f"block_m={e['block_m']} block_d={e['block_d']} "
                     f"({e['speedup_vs_default']}x vs default)"))
    rows.append(("tune.speedup_vs_default",
                 payload["tuned_speedup_vs_default"], "x (geomean, gated)"))
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")
    return rows


def run_distributed_bench(n_devices: int = 8, n_mules: int = 64,
                          steps: int = 400, n_seeds: int = 4,
                          out_path: str = _DEFAULT_DIST_OUT):
    """Mule-sharded replay: per-step shard_map dispatch loop vs one scan."""
    import numpy as np
    from repro.core.distributed import DistributedConfig, to_distributed_state
    from repro.scenarios import run_population_distributed_loop

    out_path = os.path.abspath(out_path)    # the child runs with cwd=root
    if jax.device_count() < n_devices:
        # the force-host-devices flag only raises the CPU platform's count;
        # if the child still lands here (e.g. a GPU backend), bail instead
        # of respawning forever
        if os.environ.get("_REPRO_DIST_BENCH_CHILD"):
            raise RuntimeError(
                f"need >= {n_devices} devices but forcing host devices "
                f"yielded {jax.device_count()} on backend "
                f"{jax.default_backend()!r}; run on a CPU host or a "
                f"machine with enough accelerators")
        _respawn_with_devices(n_devices, out_path)
        with open(out_path) as f:            # the child's recorded numbers
            payload = json.load(f)
        return [(k, v, "from respawned child") for k, v in payload.items()
                if isinstance(v, (int, float))]

    mesh = jax.make_mesh((2, n_devices // 2), ("pod", "data"))
    pop, co, batch_fn, train_fn, pcfg = _setup(n_mules=n_mules, steps=steps)
    key = jax.random.PRNGKey(7)

    # -- per-step path: one jitted shard_map dispatch per step ---------------
    # (run_population_distributed_loop — same method step as the scan, so
    # the measured gap is purely the dispatch tax)
    dcfg_ms = DistributedConfig(pop=PopulationConfig(
        mode=pcfg.mode, n_fixed=pcfg.n_fixed, n_mules=pcfg.n_mules,
        freshness=FreshnessConfig(stat="meanstd")))

    def loop(n):
        st = to_distributed_state(pop, dcfg_ms)
        co_n = {k: np.asarray(v)[:n] if np.asarray(v).ndim == 2 else v
                for k, v in co.items()}
        final, _ = run_population_distributed_loop(st, co_n, batch_fn,
                                                   train_fn, dcfg_ms, mesh,
                                                   key)
        jax.block_until_ready(jax.tree.leaves(final["mule_models"])[0])

    loop(3)                                     # compile
    t0 = time.perf_counter()
    loop(steps)
    loop_s = time.perf_counter() - t0

    # -- scan path: the whole replay is one program --------------------------
    dstate = to_distributed_state(pop, dcfg_ms)
    jit_cache_clear()
    t0 = time.perf_counter()
    _block(run_population_distributed(dstate, co, batch_fn, train_fn,
                                      dcfg_ms, mesh, key)[0])
    scan_cold_s = time.perf_counter() - t0
    before = jit_cache_stats()["traces"]
    t0 = time.perf_counter()
    _block(run_population_distributed(dstate, co, batch_fn, train_fn,
                                      dcfg_ms, mesh, key)[0])
    scan_warm_s = time.perf_counter() - t0
    retraces = jit_cache_stats()["traces"] - before
    assert retraces == 0, "warm distributed replay retraced"

    # paper-semantics filter (median/MAD sketch) on the same workload
    dcfg_med = DistributedConfig(pop=pcfg)      # stat="median" default
    dstate_med = to_distributed_state(pop, dcfg_med)
    _block(run_population_distributed(dstate_med, co, batch_fn, train_fn,
                                      dcfg_med, mesh, key)[0])
    t0 = time.perf_counter()
    _block(run_population_distributed(dstate_med, co, batch_fn, train_fn,
                                      dcfg_med, mesh, key)[0])
    scan_med_s = time.perf_counter() - t0

    # -- distributed sweep: vmapped seeds must equal sequential runs ---------
    seeds = list(range(n_seeds))
    setups = [_setup(n_mules=n_mules, steps=steps // 4, seed=s)
              for s in seeds]
    keys = [jax.random.PRNGKey(1000 + s) for s in seeds]
    finals = [run_population_distributed(
        to_distributed_state(st, dcfg_med), sco, batch_fn, train_fn,
        dcfg_med, mesh, k)[0] for (st, sco, _, _, _), k in zip(setups, keys)]
    states = stack_trees([to_distributed_state(s[0], dcfg_med)
                          for s in setups])
    cos = stack_colocations([s[1] for s in setups])
    vf, _ = run_sweep_distributed(states, cos, batch_fn, train_fn, dcfg_med,
                                  mesh, stack_trees(keys))
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for i in range(n_seeds)
        for a, b in zip(jax.tree.leaves(jax.tree.map(lambda l: l[i], vf)),
                        jax.tree.leaves(finals[i])))
    assert bitwise, "distributed sweep diverged from sequential runs"

    speedup = loop_s / scan_warm_s
    rows = [
        (f"dist.per_step_loop.T{steps}", loop_s, "s total"),
        (f"dist.scan_cold.T{steps}", scan_cold_s, "s total"),
        (f"dist.scan_warm.T{steps}", scan_warm_s, "s total"),
        (f"dist.scan_warm_median.T{steps}", scan_med_s,
         "s total (median/MAD sketch)"),
        (f"dist.speedup.T{steps}", speedup, "x (per-step/scan-warm)"),
        ("dist.retraces_second_call", retraces, "count"),
        ("dist.sweep_bitwise_equal", int(bitwise), "bool"),
    ]
    for name, val, derived in rows:
        print(f"{name},{val:.3f},{derived}" if isinstance(val, float)
              else f"{name},{val},{derived}")

    payload = {
        "bench": "engine_micro.run_distributed_bench",
        "config": {"n_devices": n_devices, "mesh": dict(mesh.shape),
                   "n_mules": n_mules, "steps": steps, "n_seeds": n_seeds,
                   "method": "mlmule", "backend": jax.default_backend()},
        "per_step_loop_s": round(loop_s, 4),
        "scan_cold_s": round(scan_cold_s, 4),
        "scan_warm_s": round(scan_warm_s, 4),
        "scan_warm_median_sketch_s": round(scan_med_s, 4),
        "speedup_vs_per_step": round(speedup, 2),
        "retraces_second_call": int(retraces),
        "sweep_bitwise_equal": bool(bitwise),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return rows


_SCALE_MARK = "SCALE_CHILD_RESULT "


def _scale_workload(n_mules: int):
    """Linear mule-regression workload for the scale sweep: per-step cost
    is dominated by population/exchange machinery, not model FLOPs, so
    steps/sec tracks the engine, and batches are sampled inside the scan
    (no [M, dataset] tensor competing with the schedule for RSS)."""
    d = 8

    def train_fn(params, b, k):
        xb, yb = b
        g = jax.grad(lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(params)
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

    def batch_fn(k, t):
        kx, ky = jax.random.split(k)
        return {"fixed": None,
                "mule": (jax.random.normal(kx, (n_mules, 2, d)),
                         jax.random.normal(ky, (n_mules, 2)))}

    pcfg = PopulationConfig(mode="mobile", n_fixed=8, n_mules=n_mules)

    def init_pop():
        return init_population(
            jax.random.PRNGKey(1),
            lambda k: {"w": jax.random.normal(k, (d,))}, pcfg)

    return init_pop, batch_fn, train_fn, pcfg


def _scale_child(cfg_json: str) -> None:
    """One (M, engine-mode) measurement, isolated in its own process so
    ``ru_maxrss`` is that engine's peak alone and the two modes can't share
    XLA allocations. Prints one marked JSON line the parent parses.

    Mode ``stream_mp`` is one *rank* of a ``jax.distributed`` cluster
    spawned by ``_spawn_scale_child_cluster``: the coordinator triple
    arrives on the ``REPRO_MP_*`` env vars, so ``initialize_from_env``
    must run before the first jax computation. Every rank prints its own
    result line (digest of the process-allgathered final weights, its own
    peak RSS) and the parent cross-checks the digests."""
    import hashlib
    import resource

    import numpy as np

    from repro.launch.multiprocess import initialize_from_env
    initialize_from_env()

    from repro.mobility import commuter_stream, materialize_generator
    from repro.scenarios import run_population_streamed

    cfg = json.loads(cfg_json)
    m, steps = int(cfg["m"]), int(cfg["steps"])
    chunk_len, mode = int(cfg["chunk_len"]), cfg["mode"]
    init_pop, batch_fn, train_fn, pcfg = _scale_workload(m)
    key = jax.random.PRNGKey(42)
    gen = commuter_stream(0, m, steps)

    retraces = None
    w_host = None
    if mode == "stream_mp":
        # one rank of the multi-process mesh: same streamed engine, same
        # generator, but the chunk replay runs under shard_map over a
        # (1, global-device-count) mule mesh spanning every process
        from jax.experimental import multihost_utils

        from repro.core.distributed import (DistributedConfig,
                                            to_distributed_state)
        from repro.launch.mesh import make_mule_mesh

        mesh = make_mule_mesh(1, jax.device_count())
        dcfg = DistributedConfig(pop=pcfg)
        sched_bytes = gen.schedule_bytes() + chunk_len * m * 14

        def run(g):
            return run_population_streamed(
                to_distributed_state(init_pop(), dcfg), g, batch_fn,
                train_fn, pcfg, key, chunk_len=chunk_len,
                mesh=mesh, dcfg=dcfg)

        _block(run(gen)[0])
        t0 = time.perf_counter()
        final, _ = run(gen)
        _block(final)
        dt = time.perf_counter() - t0
        # horizon-free check, attributable per rank: each process has its
        # own jit cache, so the prefixed counters pin each rank to zero
        pid = jax.process_index()
        before = jit_cache_stats(per_process=True)[f"p{pid}/traces"]
        gen2 = commuter_stream(0, m, (steps // 2) // chunk_len * chunk_len)
        _block(run(gen2)[0])
        retraces = (jit_cache_stats(per_process=True)[f"p{pid}/traces"]
                    - before)
        # every rank hashes the SAME global weights: allgather across the
        # cluster, so digest equality across ranks is bitwise cross-process
        # parity of the final models
        w_host = multihost_utils.process_allgather(
            final["mule_models"]["w"], tiled=True)
    elif mode == "stream":
        # schedule memory: the generator's O(M) params + the [chunk, M]
        # slices live inside one compiled dispatch (fid 4B + exch 1B +
        # pos 8B + active 1B per cell)
        sched_bytes = gen.schedule_bytes() + chunk_len * m * 14
        _block(run_population_streamed(init_pop(), gen, batch_fn, train_fn,
                                       pcfg, key, chunk_len=chunk_len)[0])
        t0 = time.perf_counter()
        final, _ = run_population_streamed(init_pop(), gen, batch_fn,
                                           train_fn, pcfg, key,
                                           chunk_len=chunk_len)
        _block(final)
        dt = time.perf_counter() - t0
        # the compiled chunk program must be horizon-free: a half-length
        # generator replays through the same cache entry, zero new traces
        before = jit_cache_stats()["traces"]
        gen2 = commuter_stream(0, m, (steps // 2) // chunk_len * chunk_len)
        _block(run_population_streamed(init_pop(), gen2, batch_fn, train_fn,
                                       pcfg, key, chunk_len=chunk_len)[0])
        retraces = jit_cache_stats()["traces"] - before
    else:
        co = materialize_generator(gen, chunk_len=max(chunk_len, 64))
        sched_bytes = sum(
            np.asarray(co[k]).nbytes
            for k in ("fixed_id", "exchange", "pos", "active", "area"))
        _block(run_population(init_pop(), co, batch_fn, train_fn, pcfg, key,
                              donate=True)[0])
        t0 = time.perf_counter()
        final, _ = run_population(init_pop(), co, batch_fn, train_fn, pcfg,
                                  key, donate=True)
        _block(final)
        dt = time.perf_counter() - t0

    if w_host is None:
        w_host = np.asarray(final["mule_models"]["w"])
    w = np.ascontiguousarray(np.asarray(w_host, np.float32))
    out = {
        "m": m, "mode": mode,
        "steps_per_sec": steps / dt, "wall_s": dt,
        "schedule_bytes": int(sched_bytes),
        "peak_rss_mb": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1024.0,   # linux: KB units
        "digest": hashlib.sha256(w.tobytes()).hexdigest(),
    }
    if mode == "stream_mp":
        out["process_id"] = int(jax.process_index())
        out["n_processes"] = int(jax.process_count())
    if retraces is not None:
        out["retraces_new_t"] = int(retraces)
    print(_SCALE_MARK + json.dumps(out))


def _child_env() -> dict:
    """Env for scale children: repo root + src on PYTHONPATH so
    ``-m benchmarks.engine_micro`` resolves regardless of cwd."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep + root +
                         os.pathsep +
                         env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    return env


def _spawn_scale_child(cfg: dict) -> dict:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-m", "benchmarks.engine_micro",
                          "--scale-child", json.dumps(cfg)],
                         env=_child_env(), cwd=root, check=True,
                         capture_output=True, text=True)
    for line in res.stdout.splitlines():
        if line.startswith(_SCALE_MARK):
            return json.loads(line[len(_SCALE_MARK):])
    raise RuntimeError(f"scale child produced no result:\n"
                       f"{res.stdout}\n{res.stderr}")


def _spawn_scale_child_cluster(cfg: dict, n_processes: int,
                               devices_per_process: int = 1) -> list:
    """Run one ``stream_mp`` measurement as an N-process local cluster.

    ``spawn_local_cluster`` launches every rank concurrently (the
    coordinator blocks until the whole cluster dials in); each rank
    prints its own marked result line and this returns them sorted by
    rank. Any rank failing (non-zero exit or no result line) raises with
    that rank's merged stdout/stderr."""
    from repro.launch.multiprocess import spawn_local_cluster

    results = spawn_local_cluster(
        [sys.executable, "-m", "benchmarks.engine_micro",
         "--scale-child", json.dumps(cfg)],
        n_processes, devices_per_process,
        base_env=_child_env(), timeout=3600)
    ranks = []
    for pid, res in enumerate(results):
        if res.returncode != 0:
            raise RuntimeError(f"scale cluster rank {pid} exited "
                               f"{res.returncode}:\n{res.stdout}")
        for line in res.stdout.splitlines():
            if line.startswith(_SCALE_MARK):
                ranks.append(json.loads(line[len(_SCALE_MARK):]))
                break
        else:
            raise RuntimeError(f"scale cluster rank {pid} produced no "
                               f"result:\n{res.stdout}")
    return sorted(ranks, key=lambda r: r["process_id"])


def run_scale_bench(ms=(10_000, 32_000, 100_000), steps: int = 96,
                    chunk_len: int = 8, out_path: str = _DEFAULT_SCALE_OUT,
                    mp_m: int = 1_000_000, mp_processes: int = 2,
                    mp_devices_per_process: int = 1, mp_steps: int = 32):
    """Population-scale curve: streamed vs materialized engine over M.

    Per M (each mode in its own subprocess for honest peak-RSS):

    - **stream** — ``run_population_streamed`` over the procedural
      ``commuter_stream`` generator; schedule memory is the generator's
      O(M) params plus one [chunk, M] slice. The child also proves the
      chunk program is horizon-free (a half-length replay adds zero
      traces, reported as ``retraces_new_t``).
    - **materialized** — ``run_population`` over
      ``materialize_generator(...)``'s full ``[T, M]`` tensors, the
      classic engine and the parity reference.

    Parity is cross-process: both children hash their final mule models
    (XLA CPU is deterministic) and the digests must match at EVERY M —
    streaming changes memory, never results. The bench asserts schedule
    bytes stay T-free on the stream side (O(chunk·M) vs the materialized
    O(T·M)) and records both RSS peaks; the gated headline is streamed
    steps/sec at the largest M (``BENCH_scale.json``).

    The curve then extends past single-process: ``mp_processes`` ranks
    are spawned as a local ``jax.distributed`` cluster
    (``_spawn_scale_child_cluster``) running the streamed engine over a
    multi-host mule mesh at ``mp_m`` mules (``mp_steps`` steps — the
    point is scale, not horizon). Every rank hashes the process-
    allgathered final weights; the digests must agree bitwise across
    ranks (``parity_sha_ok``) and each rank's half-horizon replay must
    add zero traces. The multi-process row becomes ``max_m`` and the
    gated ``steps_per_sec_at_max_m`` headline; the ``*_at_max_m``
    memory/schedule keys keep reporting the largest row that has BOTH
    engine modes (the stream-vs-materialized comparison only exists
    single-process — materializing a [T, 10^6] schedule is the thing
    this engine exists to avoid).
    """
    _require_host_cpu("engine_micro --scale")
    out_path = os.path.abspath(out_path)
    ms = sorted(int(m) for m in ms)
    curve = []
    for m in ms:
        base = {"m": m, "steps": steps, "chunk_len": chunk_len}
        s = _spawn_scale_child({**base, "mode": "stream"})
        r = _spawn_scale_child({**base, "mode": "materialized"})
        assert s["digest"] == r["digest"], \
            f"M={m}: streamed models != materialized models (parity broken)"
        assert s["retraces_new_t"] == 0, \
            f"M={m}: chunk program retraced on a new horizon"
        assert s["schedule_bytes"] < r["schedule_bytes"], \
            f"M={m}: streaming failed to shrink the schedule"
        row = {
            "m": m,
            "stream_steps_per_sec": round(s["steps_per_sec"], 2),
            "materialized_steps_per_sec": round(r["steps_per_sec"], 2),
            "stream_schedule_bytes": s["schedule_bytes"],
            "materialized_schedule_bytes": r["schedule_bytes"],
            "peak_rss_stream_mb": round(s["peak_rss_mb"], 1),
            "peak_rss_materialized_mb": round(r["peak_rss_mb"], 1),
            "parity_bitwise": True,
            "retraces_new_t": s["retraces_new_t"],
        }
        curve.append(row)
        print(f"scale.M{m}: stream {row['stream_steps_per_sec']:.1f} "
              f"steps/s ({row['stream_schedule_bytes'] / 1e6:.1f} MB sched, "
              f"rss {row['peak_rss_stream_mb']:.0f} MB) | materialized "
              f"{row['materialized_steps_per_sec']:.1f} steps/s "
              f"({row['materialized_schedule_bytes'] / 1e6:.1f} MB sched, "
              f"rss {row['peak_rss_materialized_mb']:.0f} MB) | parity OK")

    sp_top = curve[-1]
    mp_row = None
    if mp_processes and mp_processes > 1:
        ranks = _spawn_scale_child_cluster(
            {"m": int(mp_m), "steps": int(mp_steps),
             "chunk_len": chunk_len, "mode": "stream_mp"},
            mp_processes, mp_devices_per_process)
        parity_sha_ok = len({r["digest"] for r in ranks}) == 1
        assert parity_sha_ok, \
            (f"M={mp_m}: final-weight digests diverged across ranks: "
             f"{[r['digest'][:12] for r in ranks]}")
        assert all(r["retraces_new_t"] == 0 for r in ranks), \
            f"M={mp_m}: a rank's chunk program retraced on a new horizon"
        r0 = ranks[0]
        mp_row = {
            "m": int(mp_m), "mode": "stream_mp",
            "n_processes": int(mp_processes),
            "stream_steps_per_sec": round(r0["steps_per_sec"], 2),
            "stream_schedule_bytes": r0["schedule_bytes"],
            "rss_per_process_mb": [round(r["peak_rss_mb"], 1)
                                   for r in ranks],
            "parity_sha_ok": parity_sha_ok,
            "retraces_new_t": max(r["retraces_new_t"] for r in ranks),
        }
        curve.append(mp_row)
        print(f"scale.M{int(mp_m)}.x{mp_processes}proc: stream "
              f"{mp_row['stream_steps_per_sec']:.2f} steps/s "
              f"({mp_row['stream_schedule_bytes'] / 1e6:.1f} MB sched, "
              f"rss/proc {mp_row['rss_per_process_mb']} MB) | "
              f"cross-process sha parity OK")

    top = curve[-1]
    payload = {
        "bench": "engine_micro.run_scale_bench",
        "config": {"ms": ms, "steps": steps, "chunk_len": chunk_len,
                   "scenario": "streaming_commuter", "method": "mlmule",
                   "model": "linear_d8", "backend": jax.default_backend(),
                   "mp_m": int(mp_m), "mp_processes": int(mp_processes),
                   "mp_devices_per_process": int(mp_devices_per_process),
                   "mp_steps": int(mp_steps)},
        "curve": curve,
        "max_m": top["m"],
        "steps_per_sec_at_max_m": top["stream_steps_per_sec"],
        "parity_bitwise_all_m": all(r["parity_bitwise"] for r in curve
                                    if "parity_bitwise" in r),
        # memory/schedule comparisons need both engine modes, which only
        # the single-process rows have — these keys stay pinned to the
        # largest such row even when the mp row extends max_m past it
        "stream_schedule_bytes_at_max_m": sp_top["stream_schedule_bytes"],
        "materialized_schedule_bytes_at_max_m":
            sp_top["materialized_schedule_bytes"],
        "schedule_bytes_ratio": round(
            sp_top["materialized_schedule_bytes"]
            / sp_top["stream_schedule_bytes"], 2),
        "peak_rss_stream_mb_at_max_m": sp_top["peak_rss_stream_mb"],
        "peak_rss_materialized_mb_at_max_m":
            sp_top["peak_rss_materialized_mb"],
        "retraces_new_t": max(r["retraces_new_t"] for r in curve),
        "n_processes": int(mp_processes) if mp_row else 1,
        "rss_per_process_mb": (mp_row["rss_per_process_mb"] if mp_row
                               else [sp_top["peak_rss_stream_mb"]]),
        "parity_sha_ok": bool(mp_row["parity_sha_ok"]) if mp_row else True,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return curve


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true",
                    help="run only the sweep benchmark")
    ap.add_argument("--distributed", action="store_true",
                    help="run only the distributed benchmark")
    ap.add_argument("--churn", action="store_true",
                    help="run only the churn-mask overhead benchmark")
    ap.add_argument("--encounter", action="store_true",
                    help="run only the encounter-mix benchmark")
    ap.add_argument("--migration", action="store_true",
                    help="run only the long-trace migration benchmark "
                         "(hop-prune rate over time with mid-run "
                         "re-bucketing on vs off; merges telemetry into "
                         "the encounter artifact — run after --encounter)")
    ap.add_argument("--roofline", action="store_true",
                    help="run only the roofline autotune sweep")
    ap.add_argument("--scale", action="store_true",
                    help="run only the population-scale curve (streamed vs "
                         "materialized engine over M, subprocess children "
                         "for peak-RSS isolation)")
    ap.add_argument("--scale-child", metavar="JSON",
                    help="internal: run one (M, mode) scale measurement in "
                         "this process and print its result line (one rank "
                         "of a cluster when spawned with REPRO_MP_* env)")
    ap.add_argument("--scale-processes", type=int, default=2,
                    help="ranks for the multi-process scale row "
                         "(0/1 skips it)")
    ap.add_argument("--scale-mp-m", type=int, default=1_000_000,
                    help="population for the multi-process scale row")
    ap.add_argument("--gate-baseline", metavar="DIR",
                    help="after producing artifacts, regression-gate them "
                         "against the committed copies in DIR "
                         "(benchmarks.bench_gate; exits non-zero on "
                         "regression)")
    ap.add_argument("--out", default=_DEFAULT_OUT)
    ap.add_argument("--out-distributed", default=_DEFAULT_DIST_OUT)
    ap.add_argument("--out-churn", default=_DEFAULT_CHURN_OUT)
    ap.add_argument("--out-encounter", default=_DEFAULT_ENC_OUT)
    ap.add_argument("--out-roofline", default=_DEFAULT_ROOF_OUT)
    ap.add_argument("--out-scale", default=_DEFAULT_SCALE_OUT)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.scale_child:
        _scale_child(args.scale_child)
        raise SystemExit(0)
    produced = []                    # (artifact name, fresh path) per bench
    if args.distributed:
        run_distributed_bench(out_path=args.out_distributed)
        produced.append(("BENCH_distributed.json", args.out_distributed))
    elif args.sweep:
        run_sweep_bench(out_path=args.out)
        produced.append(("BENCH_sweep.json", args.out))
    elif args.churn:
        run_churn_bench(out_path=args.out_churn)
        produced.append(("BENCH_churn.json", args.out_churn))
    elif args.encounter:
        run_encounter_bench(out_path=args.out_encounter)
        produced.append(("BENCH_encounter.json", args.out_encounter))
    elif args.migration:
        run_migration_bench(out_path=args.out_encounter)
        produced.append(("BENCH_encounter.json", args.out_encounter))
    elif args.roofline:
        run_roofline_bench(out_path=args.out_roofline)
        produced.append(("BENCH_roofline.json", args.out_roofline))
    elif args.scale:
        run_scale_bench(out_path=args.out_scale,
                        mp_m=args.scale_mp_m,
                        mp_processes=args.scale_processes)
        produced.append(("BENCH_scale.json", args.out_scale))
    else:
        run()
        run_donation_bench()
        run_sweep_bench(out_path=args.out)
        produced.append(("BENCH_sweep.json", args.out))
        run_churn_bench(out_path=args.out_churn)
        produced.append(("BENCH_churn.json", args.out_churn))
        run_encounter_bench(out_path=args.out_encounter)
        run_migration_bench(out_path=args.out_encounter)
        produced.append(("BENCH_encounter.json", args.out_encounter))
        run_distributed_bench(out_path=args.out_distributed)
        produced.append(("BENCH_distributed.json", args.out_distributed))
        run_roofline_bench(out_path=args.out_roofline)
        produced.append(("BENCH_roofline.json", args.out_roofline))
        run_scale_bench(out_path=args.out_scale)
        produced.append(("BENCH_scale.json", args.out_scale))
    if args.gate_baseline:
        from benchmarks import bench_gate
        results = [bench_gate.gate_artifact(
            name, bench_gate._load(os.path.join(args.gate_baseline, name)),
            bench_gate._load(path)) for name, path in produced]
        for r in results:
            print(r.row())
        if any(not r.ok for r in results):
            raise SystemExit(1)
