"""Roofline-driven autotuning over the compiled scenario engine.

Two halves, one artifact:

**Engine roofline.** ``analyze_engine_step`` compiles the scenario-engine
replay (``repro.scenarios.engine.get_compiled_replay`` — the exact program
experiments run, single-host or mule-sharded) for one (method × M × mesh)
cell, feeds the compiled HLO through the scan-aware analyzer
(``repro.launch.hlo_analysis``), and returns the three roofline terms in
seconds (compute / memory / collective against the per-chip peaks in
``repro.launch.roofline``) plus the dominant term. ``roofline_sweep`` runs
the grid of cells and is what ``benchmarks/engine_micro.py --roofline``
records.

**Kernel tuning.** ``tune_mule_agg`` / ``tune_encounter_mix`` generalize the
old hand table in ``repro.kernels.mule_agg.ops`` (one measured constant) and
the hand defaults in ``encounter_mix``: every candidate block size, as the
kernel would legalize and fit it to VMEM (``repro.kernels.tiling``), is
timed on the CPU interpret path — which tracks *relative* block behaviour,
not TPU latency, exactly like the retired ``kernels_micro.run_block_d_sweep``
— and the argmin wins. Selections land in the tuning cache section of
``benchmarks/BENCH_roofline.json``; the kernel wrappers look their block
sizes up there at call time (``tuned_block_d`` / ``tuned_encounter_blocks``)
and fall back to the old hand defaults when the cache is absent. The
kernels make whatever tile they are given legal for the TPU lowering and
fit it to VMEM (``fit_blocks``), so a lookup never needs to.

``REPRO_TUNE_CACHE`` points the lookup at a different cache file (tests use
it; an empty value disables the cache entirely).

The committed artifact is a *ratchet*: ``benchmarks/bench_gate.py`` validates
its schema on every tier-1 push and fails the CI slow lane if a freshly
produced artifact's headline metric (``tuned_speedup_vs_default`` — how much
the measured selection beats the static defaults) regresses past the
threshold. See ``benchmarks/README.md``.
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.kernels.tiling import VMEM_BUDGET_BYTES

# ---------------------------------------------------------------------------
# tuning cache: the runtime half (no jax import needed to look up a block)
# ---------------------------------------------------------------------------

_CACHE_PATH_ENV = "REPRO_TUNE_CACHE"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_PATH = os.path.join(_REPO_ROOT, "benchmarks",
                                  "BENCH_roofline.json")

_UNSET = object()
_cache_memo: Any = _UNSET

MULE_AGG_BLOCK_D_CANDIDATES = (256, 512, 1024, 2048, 4096)
ENCOUNTER_BLOCK_M_CANDIDATES = (128, 256, 512)
ENCOUNTER_BLOCK_D_CANDIDATES = (256, 512, 1024, 2048)

# the pre-tuning hand values the lookups fall back to (and the baseline the
# headline metric is measured against)
MULE_AGG_DEFAULT_BLOCK_D = 4096
ENCOUNTER_DEFAULT_BLOCKS = (256, 2048)


def tuning_cache_clear() -> None:
    """Drop the memoized cache (tests repoint ``REPRO_TUNE_CACHE``)."""
    global _cache_memo
    _cache_memo = _UNSET


def load_tuning_cache(path: Optional[str] = None) -> Optional[Dict]:
    """The parsed tuning cache, or ``None`` when unavailable.

    Resolution order: explicit ``path`` > ``REPRO_TUNE_CACHE`` (empty value
    disables) > the committed ``benchmarks/BENCH_roofline.json``. The
    default resolution is memoized; a malformed or missing file reads as
    "no cache" — autotuning must never be able to break a kernel call.
    """
    global _cache_memo
    if path is None and _cache_memo is not _UNSET:
        return _cache_memo
    resolved = path
    if resolved is None:
        resolved = os.environ.get(_CACHE_PATH_ENV)
        if resolved == "":
            _cache_memo = None
            return None
        if resolved is None:
            resolved = DEFAULT_CACHE_PATH
    try:
        with open(resolved) as f:
            cache = json.load(f)
        if not isinstance(cache.get("tuned"), dict):
            cache = None
    except (OSError, ValueError):
        cache = None
    if path is None:
        _cache_memo = cache
    return cache


def _nearest(entries: List[Dict], query: Dict[str, int]) -> Optional[Dict]:
    """Entry minimizing the summed |log shape ratio| over the query dims."""
    best, best_cost = None, None
    for e in entries:
        try:
            cost = sum(abs(math.log(max(int(e[k]), 1) / max(int(v), 1)))
                       for k, v in query.items())
        except (KeyError, TypeError, ValueError):
            continue
        if best_cost is None or cost < best_cost:
            best, best_cost = e, cost
    return best


def tuned_block_d(d: int,
                  default: int = MULE_AGG_DEFAULT_BLOCK_D) -> int:
    """``mule_agg`` D-tile size for a [M, D] population: the measured
    selection of the nearest tuned shape, else ``default``."""
    cache = load_tuning_cache()
    if cache:
        e = _nearest(cache["tuned"].get("mule_agg", []), {"d": d})
        if e and isinstance(e.get("block_d"), int):
            return e["block_d"]
    return default


def tuned_encounter_blocks(
        m: int, d: int,
        default: Tuple[int, int] = ENCOUNTER_DEFAULT_BLOCKS
) -> Tuple[int, int]:
    """``encounter_mix`` (block_m, block_d) for an [M, D] population."""
    cache = load_tuning_cache()
    if cache:
        e = _nearest(cache["tuned"].get("encounter_mix", []),
                     {"m": m, "d": d})
        if (e and isinstance(e.get("block_m"), int)
                and isinstance(e.get("block_d"), int)):
            return e["block_m"], e["block_d"]
    return default


def suggest_mesh_shape(method: str, n_mules: int,
                       path: Optional[str] = None
                       ) -> Optional[Tuple[int, int]]:
    """(pod, data) mesh shape minimizing collective+memory roofline seconds.

    Scans the cache's mesh rows (``roofline`` entries with an ``AxB`` mesh
    string — the distributed cells ``roofline_sweep`` records per shape),
    keeps the rows for ``method`` when any exist (else all mesh rows),
    takes each shape's nearest-``n_mules`` row, and returns the shape whose
    per-step ``t_collective + t_memory`` is smallest — the two terms the
    mesh shape actually moves (compute per device is shape-invariant at
    fixed chip count). Returns ``None`` without a usable cache, exactly
    like the block-size lookups: callers must keep their own fallback.
    """
    cache = load_tuning_cache(path)
    if not cache:
        return None
    rows = [r for r in cache.get("roofline", [])
            if isinstance(r, dict) and isinstance(r.get("mesh"), str)
            and "x" in r["mesh"]]
    mine = [r for r in rows if r.get("method") == method] or rows
    by_shape: Dict[str, List[Dict]] = {}
    for r in mine:
        by_shape.setdefault(r["mesh"], []).append(r)
    best, best_cost = None, None
    for shape, entries in by_shape.items():
        e = _nearest(entries, {"n_mules": n_mules})
        if e is None:
            continue
        try:
            cost = (float(e["t_collective_us_per_step"])
                    + float(e["t_memory_us_per_step"]))
            dims = tuple(int(x) for x in shape.split("x"))
        except (KeyError, TypeError, ValueError):
            continue
        if len(dims) != 2 or any(x < 1 for x in dims):
            continue
        if best_cost is None or cost < best_cost:
            best, best_cost = dims, cost
    return best


# ---------------------------------------------------------------------------
# measured kernel tuning (interpret path: relative block behaviour)
# ---------------------------------------------------------------------------


def _median_us(fn, reps: int) -> float:
    import jax
    jax.block_until_ready(fn())            # compile / first interpret pass
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e6


def tune_mule_agg(f: int = 8, m: int = 64, d: int = 65536, *,
                  reps: int = 3,
                  candidates: Sequence[int] = MULE_AGG_BLOCK_D_CANDIDATES
                  ) -> Dict:
    """Measure every feasible ``block_d`` candidate; argmin wins."""
    import jax
    from repro.kernels.mule_agg.kernel import fit_blocks, mule_agg_pallas

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    assign = jax.random.uniform(k1, (f, m))
    w = jax.random.normal(k2, (m, d))
    times: Dict[str, float] = {}
    # dedupe on the tile the kernel actually uses (legal and VMEM-fitted),
    # so tiny shapes still have at least one candidate
    for block_d in sorted({fit_blocks(f, m, d, b)[1] for b in candidates}):
        times[str(block_d)] = _median_us(
            lambda b=block_d: mule_agg_pallas(assign, w, block_d=b,
                                              interpret=True), reps)
    best = min(times, key=times.get)
    default = str(fit_blocks(f, m, d, MULE_AGG_DEFAULT_BLOCK_D)[1])
    return {"f": f, "m": m, "d": d, "block_d": int(best),
            "candidates_us": {k: round(v, 1) for k, v in times.items()},
            "speedup_vs_default": round(times[default] / times[best], 3)
            if default in times else 1.0}


def tune_encounter_mix(m: int = 1024, d: int = 480, *, reps: int = 3,
                       radius: float = 0.1,
                       block_m_candidates: Sequence[int]
                       = ENCOUNTER_BLOCK_M_CANDIDATES,
                       block_d_candidates: Sequence[int]
                       = ENCOUNTER_BLOCK_D_CANDIDATES) -> Dict:
    """Measure every feasible (block_m, block_d) candidate; argmin wins."""
    import jax
    from repro.kernels.encounter_mix.kernel import (encounter_mix_pallas,
                                                    fit_blocks)

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    pos = jax.random.uniform(ks[0], (m, 2))
    area = jax.random.randint(ks[1], (m,), 0, 2)
    active = jax.random.uniform(ks[2], (m,)) < 0.9
    w = jax.random.normal(ks[3], (m, d))
    times: Dict[str, float] = {}
    # dedupe on the tiles the kernel actually uses (legal and VMEM-fitted),
    # so tiny shapes still have at least one candidate
    fitted = lambda bm, bd: fit_blocks(m, m, d, bm, bd)[::2]
    pairs = sorted({fitted(bm, bd) for bm in block_m_candidates
                    for bd in block_d_candidates})
    for bm, bd in pairs:
        times[f"{bm}x{bd}"] = _median_us(
            lambda bm=bm, bd=bd: encounter_mix_pallas(
                pos, area, active, w, radius=radius, block_m=bm,
                block_d=bd, interpret=True)[0], reps)
    best = min(times, key=times.get)
    bm, bd = (int(x) for x in best.split("x"))
    default = "{}x{}".format(*fitted(*ENCOUNTER_DEFAULT_BLOCKS))
    return {"m": m, "d": d, "block_m": bm, "block_d": bd,
            "candidates_us": {k: round(v, 1) for k, v in times.items()},
            "speedup_vs_default": round(times[default] / times[best], 3)
            if default in times else 1.0}


# ---------------------------------------------------------------------------
# engine roofline: the compiled replay per (method × M × mesh)
# ---------------------------------------------------------------------------


def _engine_workload(n_mules: int, steps: int, seed: int = 0):
    """Tiny mobile linear-regression population (compiles in seconds but
    exercises every method's scan path, peer encounters included)."""
    import jax
    import jax.numpy as jnp
    from repro.core.population import PopulationConfig, init_population
    from repro.scenarios import walk_colocation

    X = jax.random.normal(jax.random.PRNGKey(50 + seed), (n_mules, 12, 5))
    Y = jax.random.normal(jax.random.PRNGKey(60 + seed), (n_mules, 12))

    def train_fn(params, batch, key):
        xb, yb = batch
        g = jax.grad(lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(params)
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

    def batch_fn(key, t):
        idx = jax.random.randint(key, (n_mules, 4), 0, X.shape[1])
        return {"fixed": None,
                "mule": (jnp.take_along_axis(X, idx[:, :, None], 1),
                         jnp.take_along_axis(Y, idx, 1))}

    pcfg = PopulationConfig(mode="mobile", n_fixed=4, n_mules=n_mules)
    pop = init_population(jax.random.PRNGKey(seed),
                          lambda k: {"w": jax.random.normal(k, (5,))}, pcfg)
    co = walk_colocation(seed, n_mules, steps)
    return pop, co, batch_fn, train_fn, pcfg


def analyze_engine_step(method: str, n_mules: int = 32, steps: int = 24,
                        mesh=None) -> Dict:
    """Compile the replay for one (method × M × mesh) cell and decompose it
    into roofline terms via the scan-aware HLO analyzer.

    Returns one row: per-device FLOPs/bytes/collective bytes of the WHOLE
    ``steps``-long replay (the scan trip count is multiplied in), the three
    terms in seconds against the per-chip peaks, per-step variants, and the
    dominant term. ``mesh=None`` is the single-host engine; a mesh routes
    through ``run_population_distributed``'s shard_map program instead.
    """
    import jax
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS
    from repro.scenarios.engine import (_colocation_tensors,
                                        get_compiled_replay)

    pop, co, batch_fn, train_fn, pcfg = _engine_workload(n_mules, steps)
    fid, exch, pos, area, act = _colocation_tensors(co)
    key = jax.random.PRNGKey(7)
    if mesh is None:
        chips, mesh_name, dcfg, state = 1, "1", None, pop
    else:
        from repro.core.distributed import (DistributedConfig,
                                            to_distributed_state)
        dcfg = DistributedConfig(pop=pcfg)
        state = to_distributed_state(pop, dcfg)
        chips = mesh.size
        mesh_name = "x".join(str(s) for s in mesh.shape.values())
    fn = get_compiled_replay(state, fid, exch, pos, area, act, batch_fn,
                             None, key, train_fn, pcfg, method=method,
                             eval_every=None, eval_fn=None,
                             mesh=mesh, dcfg=dcfg)
    args = (state, fid, exch, pos, area, act, None, None, key)
    compiled = fn.lower(*args).compile()
    costs = analyze_hlo(compiled.as_text())
    t_c = costs.flops / PEAK_FLOPS
    t_m = costs.bytes / HBM_BW
    t_x = costs.coll_bytes / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    return {
        "method": method, "n_mules": n_mules, "steps": steps,
        "mesh": mesh_name, "chips": chips,
        "flops_per_device": costs.flops,
        "bytes_per_device": costs.bytes,
        "coll_bytes_per_device": costs.coll_bytes,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "t_compute_us_per_step": t_c / steps * 1e6,
        "t_memory_us_per_step": t_m / steps * 1e6,
        "t_collective_us_per_step": t_x / steps * 1e6,
        "dominant": max(terms, key=terms.get),
    }


def roofline_sweep(methods: Optional[Sequence[str]] = None,
                   mule_counts: Sequence[int] = (32, 128),
                   steps: int = 24,
                   mesh=None, meshes: Sequence = (),
                   mesh_methods: Sequence[str] = ("mlmule", "gossip"),
                   mesh_mules: int = 64) -> List[Dict]:
    """The (method × M × mesh) grid behind ``BENCH_roofline.json``.

    Single-host rows for every method at every ``mule_counts``; distributed
    rows for ``mesh_methods`` at ``mesh_mules`` on every supplied mesh
    (``mesh`` is the legacy single-mesh spelling; ``meshes`` records one
    row set per shape so ``suggest_mesh_shape`` has real alternatives to
    rank). Collective terms are zero on the single-host rows by
    construction.
    """
    from repro.core.population import METHODS_MOBILE

    if methods is None:
        methods = METHODS_MOBILE
    rows = [analyze_engine_step(m, n, steps)
            for m in methods for n in mule_counts]
    all_meshes = list(meshes) + ([mesh] if mesh is not None else [])
    for ms in all_meshes:
        rows += [analyze_engine_step(m, mesh_mules, steps, mesh=ms)
                 for m in mesh_methods]
    return rows


def _geomean(xs: Sequence[float]) -> float:
    xs = [max(x, 1e-12) for x in xs]
    return math.exp(sum(math.log(x) for x in xs) / len(xs)) if xs else 1.0


def run_roofline(out_path: str = DEFAULT_CACHE_PATH, *, reps: int = 3,
                 steps: int = 24, mule_counts: Sequence[int] = (32, 128),
                 methods: Optional[Sequence[str]] = None, mesh=None,
                 meshes: Sequence = (),
                 mule_agg_shapes: Sequence[Tuple[int, int, int]]
                 = ((8, 64, 4096), (8, 64, 65536)),
                 encounter_shapes: Sequence[Tuple[int, int]]
                 = ((512, 480), (2048, 480))) -> Dict:
    """Produce the full artifact: roofline grid + tuning cache + headline.

    The headline metric — ``tuned_speedup_vs_default``, the geometric mean
    over all tuned shapes of (default-block time / selected-block time) —
    is what ``bench_gate`` ratchets: it can only regress if the measured
    selection stops beating the static hand defaults.
    """
    import jax

    rows = roofline_sweep(methods=methods, mule_counts=mule_counts,
                          steps=steps, mesh=mesh, meshes=meshes)
    tuned_ma = [tune_mule_agg(f, m, d, reps=reps)
                for f, m, d in mule_agg_shapes]
    tuned_em = [tune_encounter_mix(m, d, reps=reps)
                for m, d in encounter_shapes]
    headline = _geomean([e["speedup_vs_default"]
                         for e in tuned_ma + tuned_em])
    payload = {
        "bench": "autotune.run_roofline",
        "config": {
            "backend": jax.default_backend(),
            "reps": reps, "steps": steps,
            "mule_counts": list(mule_counts),
            "mesh": (None if mesh is None
                     else "x".join(str(s) for s in mesh.shape.values())),
            "meshes": ["x".join(str(s) for s in ms.shape.values())
                       for ms in meshes],
            "vmem_budget_bytes": VMEM_BUDGET_BYTES,
        },
        "roofline": [
            {k: (round(v, 6) if isinstance(v, float) else v)
             for k, v in r.items()} for r in rows
        ],
        "tuned": {"mule_agg": tuned_ma, "encounter_mix": tuned_em},
        "tuned_speedup_vs_default": round(headline, 3),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return payload
