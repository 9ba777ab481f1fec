"""Smoke test of the ML Mule replay on a TPU: the quickest proof that the
system still starts on the chip and computes what it computes on the CPU.

  python chip_smoke.py                # one chip: phases a-e
  python chip_smoke.py --four-chips   # a four-chip host: the sharded engine

It drives the engine's own entry points (``init_population``,
``run_population``, ``run_population_streamed``,
``run_population_distributed``) with the paper's mule CNN at its published
widths (``CNNConfig()``: 32 px, conv (32, 64), hidden 128 — 546,484
parameters per mule), random weights, synthetic images
(``make_image_dataset``) partitioned Shards-style over the spaces, and the
``commuter`` colocation scenario, all made from ``--seed``. The one-chip
population is M=512 mules over F=8 spaces in mobile mode: the largest
power of two whose replay fits one v5e chip's 16 GB.

One chip:
  a. ``mlmule`` through ``run_population(donate=True)``; a second replay of
     the same inputs must agree bitwise.
  b. the same replay through ``run_population_streamed``, bitwise equal
     to (a).
  c. ``gossip`` through ``run_population``.
  d. the ``mule_agg`` and ``encounter_mix`` Pallas kernels compiled for the
     chip at D=546,484, against their jnp references: at M=512 on the
     weights (a) trained, and at M=1100 on random weights, where the
     reduction over mules takes three tiles and the last one is ragged.
  e. a 16-mule replay of ``mlmule`` and ``gossip`` on the chip and on the
     host's CPU backend, from the same initial state.

Four chips: ``mlmule`` and ``gossip`` through
``run_population_distributed`` on a 1x4 mule mesh against the same engine
on a one-chip mesh at M=512 (six steps, f32 matmul precision on both
sides), then M=2048 (512 mules per chip) on the 1x4 mesh, checking through
``addressable_shards`` that each chip holds a quarter of the mule state.

Each phase prints one line: what it checked, the largest difference it
found, and its tolerance with the reason where the check is not bitwise.
Times and memory on those lines are smoke figures, not benchmark metrics.
The last line of a run in which every phase passed is
``{"ok": true, "device": {...}}``; a failed phase, or a machine without a
TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

# the small-M parity phase needs the host's CPU backend next to the chip
if os.environ.get("JAX_PLATFORMS") == "tpu":
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.mule_cnn import CNNConfig  # noqa: E402
from repro.core import PopulationConfig, init_population  # noqa: E402
from repro.data import make_image_dataset, shards_partition  # noqa: E402
from repro.models.cnn import cnn_forward, init_cnn, xent_loss  # noqa: E402
from repro.scenarios import (get_scenario, run_population,  # noqa: E402
                             run_population_streamed, scenario_generator)

N_FIXED = 8          # the commuter scenario's spaces (2 areas x 4)
BATCH = 16           # images per mule per step
POOL = 32            # training images held by each mule
LR = 0.05
STEPS = 24           # replay length: 8 gossip exchanges, 3 streamed chunks
MULES = 512          # one chip's population
RAGGED_MULES = 1100  # (d) again: three 512-mule reduction tiles, last ragged
MESH_STEPS = 6       # four chips: length of the 1x4 vs 1x1 comparison

# Not bitwise: the compared programs are compiled separately (other shapes,
# other fusions), and XLA:TPU runs f32 convolutions and matmuls at its
# default precision, which rounds operands to bf16 (8-bit mantissa) on the
# MXU. Each rounding moves a value by at most ~2^-9 of itself and the
# replay averages many of them, so 1e-2 of the largest |weight| passes
# precision effects and fails a wrong aggregation, mix or tile, which moves
# weights by their own magnitude.
TOL_PRECISION = 1e-2

# For the mesh comparison, run inside a ``float32`` matmul-precision scope
# on both sides: what remains is the order of f32 sums (other conv tilings
# per shard shape, the ring's blockwise accumulation). Training is chaotic
# in a few units — a last-bit difference flips a ReLU or max-pool choice
# and that unit's weights part far more than the rest — and the parting
# grows with the replay, so the check runs ``MESH_STEPS`` steps and bounds
# the population's relative RMS difference ||a - b|| / ||b|| rather than
# the largest single weight. Rounding alone gave 6.2e-7 (mlmule) and
# 1.9e-6 (gossip) on a v5e at M=512, and up to 3.2e-5 (gossip) on four CPU
# devices at M=64; a sharding fault planted on those CPU devices gave 1.5e-2
# (batch rows sliced one off on three shards) to 1.5e-1 (the last ring hop
# dropped). 1e-4 sits above every clean reading and 150x below the faults.
TOL_F32_RMS = 1e-4


@dataclasses.dataclass
class Workload:
    """One replay's inputs, every array made from ``seed``."""
    pcfg: PopulationConfig
    colocation: Dict[str, Any]
    init_model: Callable
    train_fn: Callable
    batch_fn: Callable
    context: Dict[str, Any]
    key: Any
    seed: int
    n_steps: int


def make_workload(seed: int, n_mules: int, n_steps: int,
                  cnn: CNNConfig = CNNConfig(), device=None) -> Workload:
    """Mule CNN + Shards image pools + commuter colocation, from ``seed``.

    ``device`` places the dataset (default: JAX's default device). The
    images and pools reach ``batch_fn`` through the engine's ``context``
    argument, so a compiled replay holds no copy of them."""
    co = get_scenario("commuter").colocation(seed, n_mules, n_steps)
    x, sup, sub = make_image_dataset(seed, n_per_sub=16,
                                     n_super=cnn.n_classes,
                                     size=cnn.image_size)
    sh = shards_partition(sup, sub, n_areas=2, seed=seed)
    rng = np.random.default_rng(seed)
    pools = np.stack([
        rng.choice(np.concatenate([sh["space_idx"][(int(a), int(s))],
                                   sh["general_idx"][(int(a), int(s))]]),
                   POOL)
        for a, s in zip(co["init_area"], co["init_space"])]).astype(np.int32)
    context = jax.device_put({"x": x, "y": sup, "pools": pools}, device)

    def batch_fn(key, t, ctx):
        j = jax.random.randint(key, (n_mules, BATCH), 0, POOL)
        idx = jnp.take_along_axis(ctx["pools"], j, axis=1)
        return {"fixed": None, "mule": (ctx["x"][idx], ctx["y"][idx])}

    def train_fn(params, batch, key):
        xb, yb = batch
        g = jax.grad(lambda p: xent_loss(cnn_forward(p, xb), yb))(params)
        return jax.tree.map(lambda p, gg: p - LR * gg, params, g)

    pcfg = PopulationConfig(mode="mobile", n_fixed=N_FIXED, n_mules=n_mules)
    return Workload(pcfg, co, lambda k: init_cnn(k, cnn), train_fn, batch_fn,
                    context, jax.random.PRNGKey(seed + 1), seed, n_steps)


def initial_state(w: Workload):
    return init_population(jax.random.PRNGKey(w.seed), w.init_model, w.pcfg)


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    ok: bool
    detail: str

    def line(self) -> str:
        return f"[{self.name}] {'PASS' if self.ok else 'FAIL'}: {self.detail}"


def _block(tree):
    jax.block_until_ready(tree)
    return tree


def compare(a, b) -> Dict[str, Any]:
    """Leaf-by-leaf on the host: bitwise equality, max |a - b|, max |b|,
    the relative RMS difference ||a - b|| / ||b||, and whether both are
    finite."""
    out = {"bitwise": True, "max_diff": 0.0, "scale": 0.0, "finite": True}
    sq_diff = sq_ref = 0.0
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        na, nb = np.asarray(la).ravel(), np.asarray(lb).ravel()
        out["bitwise"] &= bool(np.array_equal(na, nb))
        out["finite"] &= bool(np.isfinite(na).all() and np.isfinite(nb).all())
        if na.size:
            diff = np.abs(na - nb)
            out["max_diff"] = max(out["max_diff"], float(diff.max()))
            out["scale"] = max(out["scale"], float(np.abs(nb).max()))
            sq_diff += float(np.vdot(diff, diff))
            sq_ref += float(np.vdot(nb, nb))
    out["rel_rms"] = (sq_diff / sq_ref) ** 0.5 if sq_ref else 0.0
    return out


def _within(c: Dict[str, Any], tol: float) -> bool:
    return c["finite"] and (c["bitwise"] or c["max_diff"] <= tol * c["scale"])


def _describe(c: Dict[str, Any], tol: Optional[float]) -> str:
    if c["bitwise"]:
        return "bitwise equal"
    s = f"max |diff| {c['max_diff']:.3e} of max |w| {c['scale']:.3e}"
    return s if tol is None else f"{s} (tolerance {tol:g} x max |w|)"


def _shape_ok(state, n_mules: int) -> bool:
    return all(l.shape[0] == n_mules
               for l in jax.tree.leaves(state["mule_models"]))


def _n_params(models) -> int:
    return sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(models))


def _peak() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2 ** 30:.2f} GiB"


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_mlmule(w: Workload):
    """(a): two donated replays of the same inputs; returns the check and
    the first replay's final mule models, copied to the host so that the
    later phases have the chip's memory to themselves."""
    m, t = w.pcfg.n_mules, w.n_steps
    runs, secs = [], []
    for _ in range(2):
        state = initial_state(w)
        t0 = time.perf_counter()
        final, _ = run_population(state, w.colocation, w.batch_fn,
                                  w.train_fn, w.pcfg, w.key, method="mlmule",
                                  context=w.context, donate=True)
        _block(final)
        secs.append(time.perf_counter() - t0)
        shape_ok = _shape_ok(final, m)
        runs.append(jax.device_get(final["mule_models"]))
        del state, final
    c = compare(runs[1], runs[0])
    init = compare(initial_state(w)["mule_models"], runs[0])
    ok = c["finite"] and c["bitwise"] and shape_ok and not init["bitwise"]
    detail = (f"mlmule run_population(donate=True) M={m} T={t} "
              f"D={_n_params(runs[0])}: finite, [{m}, ...] mule state, "
              f"trained away from init; second replay "
              f"{_describe(c, None)} | smoke figures: first call "
              f"{secs[0]:.1f} s (compile included), warm "
              f"{secs[1] / t:.4f} s/step, process peak {_peak()}")
    return Check("a", ok, detail), runs[0]


def phase_streamed(w: Workload, ref_models, chunk_len: int = 8) -> Check:
    """(b): the streamed engine over the same scenario, against (a)."""
    gen = scenario_generator("commuter", w.seed, w.pcfg.n_mules, w.n_steps,
                             colocation=w.colocation)
    t0 = time.perf_counter()
    final, _ = run_population_streamed(initial_state(w), gen, w.batch_fn,
                                       w.train_fn, w.pcfg, w.key,
                                       chunk_len=chunk_len, method="mlmule",
                                       context=w.context, donate=True)
    _block(final)
    secs = time.perf_counter() - t0
    c = compare(final["mule_models"], ref_models)
    # the same per-step program on the same chip: bitwise, as in (a)
    return Check("b", c["finite"] and c["bitwise"],
                 f"mlmule run_population_streamed(chunk_len={chunk_len}) vs "
                 f"(a): {_describe(c, None)} | smoke figures: "
                 f"{secs:.1f} s (compile included), process peak {_peak()}")


def phase_gossip(w: Workload, mlmule_models) -> Check:
    """(c): gossip through the materialized engine."""
    t0 = time.perf_counter()
    final, _ = run_population(initial_state(w), w.colocation, w.batch_fn,
                              w.train_fn, w.pcfg, w.key, method="gossip",
                              context=w.context, donate=True)
    _block(final)
    secs = time.perf_counter() - t0
    init = compare(initial_state(w)["mule_models"], final["mule_models"])
    vs_ml = compare(final["mule_models"], mlmule_models)
    ok = init["finite"] and _shape_ok(final, w.pcfg.n_mules) \
        and not init["bitwise"] and not vs_ml["bitwise"]
    return Check("c", ok,
                 f"gossip run_population M={w.pcfg.n_mules} T={w.n_steps}: "
                 "finite, trained away from init and from (a) | smoke "
                 f"figures: {secs:.1f} s (compile included), process peak "
                 f"{_peak()}")


def phase_kernels(weights, seed: int,
                  interpret: bool = False) -> List[Check]:
    """(d): ``mule_agg`` and ``encounter_mix`` against their references.

    The references run inside a ``float32`` matmul-precision scope (this
    comparison only), so they are the exact f32 result; the kernels'
    default-precision MXU passes are held to ``TOL_PRECISION``. The
    encounter counts are sums of 0/1 and must match exactly."""
    from repro.kernels.encounter_mix import kernel as enc
    from repro.kernels.encounter_mix.ref import encounter_mix_reference
    from repro.kernels.mule_agg import kernel as agg
    from repro.kernels.mule_agg.ref import mule_agg_reference
    from repro.launch.autotune import tuned_block_d, tuned_encounter_blocks

    m, d = weights.shape
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 5)
    dwell = jax.random.uniform(ks[0], (m,)) * jax.nn.one_hot(
        jax.random.randint(ks[1], (m,), 0, N_FIXED), N_FIXED).T
    assign = dwell / jnp.maximum(dwell.sum(1, keepdims=True), 1e-12)
    pos = jax.random.uniform(ks[2], (m, 2))
    area = jax.random.randint(ks[3], (m,), 0, 2)
    active = jax.random.uniform(ks[4], (m,)) < 0.9
    checks = []

    block_d = tuned_block_d(d)
    t0 = time.perf_counter()
    out = _block(agg.mule_agg_pallas(assign, weights, block_d=block_d,
                                     interpret=interpret))
    secs = time.perf_counter() - t0
    with jax.default_matmul_precision("float32"):
        ref = _block(mule_agg_reference(assign, weights))
    c = compare(out, ref)
    checks.append(Check(
        "d", _within(c, TOL_PRECISION),
        f"mule_agg_pallas(interpret={interpret}) F={N_FIXED} M={m} D={d} "
        f"tiles (block_k, block_d)={agg.fit_blocks(N_FIXED, m, d, block_d)} "
        "vs f32 reference: "
        f"{_describe(c, TOL_PRECISION)} | smoke figures: {secs:.2f} s "
        "(compile included)"))
    del out, ref

    block_m, block_d = tuned_encounter_blocks(m, d)
    t0 = time.perf_counter()
    mix, mass = _block(enc.encounter_mix_pallas(
        pos, area, active, weights, radius=0.15, block_m=block_m,
        block_d=block_d, interpret=interpret))
    secs = time.perf_counter() - t0
    with jax.default_matmul_precision("float32"):
        ref_mix, ref_mass = _block(encounter_mix_reference(
            pos, area, active, weights, radius=0.15))
    cm = compare(mass, ref_mass)
    c = compare(mix, ref_mix)
    checks.append(Check(
        "d", cm["bitwise"] and _within(c, TOL_PRECISION),
        f"encounter_mix_pallas(interpret={interpret}) M={m} D={d} tiles "
        f"(block_m, block_k, block_d)="
        f"{enc.fit_blocks(m, m, d, block_m, block_d)} vs f32 reference: "
        "encounter counts "
        f"{_describe(cm, None)} (mean {float(np.mean(ref_mass)):.1f} "
        f"peers), mix {_describe(c, TOL_PRECISION)} | smoke figures: "
        f"{secs:.2f} s (compile included)"))
    return checks


def phase_cpu_parity(seed: int, n_mules: int = 16, n_steps: int = 6,
                     cnn: CNNConfig = CNNConfig()) -> List[Check]:
    """(e): the same small replay on the default device and on the CPU
    backend, from one initial state (made on the CPU and copied over)."""
    cpu = jax.devices("cpu")[0]
    device = jax.devices()[0]
    checks = []
    for method in ("mlmule", "gossip"):
        finals = []
        for dev in (device, cpu):
            w = make_workload(seed, n_mules, n_steps, cnn, device=dev)
            with jax.default_device(cpu):
                state = initial_state(w)
            state = jax.device_put(state, dev)
            final, _ = run_population(state, w.colocation, w.batch_fn,
                                      w.train_fn, w.pcfg,
                                      jax.device_put(w.key, dev),
                                      method=method, context=w.context)
            finals.append(_block(final)["mule_models"])
        c = compare(finals[0], finals[1])
        checks.append(Check(
            "e", _within(c, TOL_PRECISION),
            f"{method} M={n_mules} T={n_steps} on {device.platform} vs "
            f"cpu: {_describe(c, TOL_PRECISION)}"))
    return checks


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def _sharded_initial_state(w: Workload, dcfg, mesh):
    """The distributed initial state, built straight into its shardings:
    mule leaves split over the mesh's data axis, the rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import to_distributed_state

    def build():
        return to_distributed_state(initial_state(w), dcfg)

    shapes = jax.eval_shape(build)
    shardings = {k: jax.tree.map(lambda _: NamedSharding(
        mesh, P(dcfg.data_axis) if k.startswith("mule") else P()), v)
        for k, v in shapes.items()}
    return jax.jit(build, out_shardings=shardings)()


def _distributed_replay(w: Workload, mesh, method: str):
    from repro.core.distributed import DistributedConfig
    from repro.scenarios import run_population_distributed

    dcfg = DistributedConfig(pop=w.pcfg)
    state = _sharded_initial_state(w, dcfg, mesh)
    t0 = time.perf_counter()
    final, _ = run_population_distributed(state, w.colocation, w.batch_fn,
                                          w.train_fn, dcfg, mesh, w.key,
                                          method=method, context=w.context,
                                          donate=True)
    _block(final)
    return final, time.perf_counter() - t0


def phase_four_chips(seed: int, n_steps: int, n_mules: int = MULES,
                     big_mules: int = 4 * MULES, n_chips: int = 4,
                     cnn: CNNConfig = CNNConfig()) -> List[Check]:
    """1 x ``n_chips`` mule mesh vs a one-chip mesh at ``n_mules`` over
    ``MESH_STEPS``, then ``big_mules`` sharded over ``n_chips`` for
    ``n_steps`` with its placement checked.

    The mesh comparison runs both sides inside a ``float32`` matmul
    precision scope (this comparison only), over six steps (six space
    exchanges, two ring exchanges), and is judged by the relative RMS
    difference (``TOL_F32_RMS``). Over 24 steps the two meshes part by
    1e-2 of max |w| at the default precision (gossip 3e-2) and still by
    3e-3 (gossip 1.8e-2) at f32: differently tiled convolutions round
    differently and training amplifies it."""
    from repro.launch.mesh import make_mule_mesh

    wide, one = make_mule_mesh(1, n_chips), make_mule_mesh(1, 1)
    w = make_workload(seed, n_mules, MESH_STEPS, cnn)
    checks = []
    for method in ("mlmule", "gossip"):
        with jax.default_matmul_precision("float32"):
            ref, _ = _distributed_replay(w, one, method)
            ref = jax.device_get(ref["mule_models"])
            out, secs = _distributed_replay(w, wide, method)
        c = compare(out["mule_models"], ref)
        del out
        checks.append(Check(
            "4", c["finite"] and (c["bitwise"]
                                  or c["rel_rms"] <= TOL_F32_RMS),
            f"{method} run_population_distributed M={n_mules} "
            f"T={MESH_STEPS} on a 1x{n_chips} mule mesh vs a 1x1 mesh, "
            f"f32 matmul precision: {_describe(c, None)}, relative RMS "
            f"difference {c['rel_rms']:.3e} (tolerance {TOL_F32_RMS:g}) | "
            f"smoke figures: {secs:.1f} s (compile included), process peak "
            f"on chip 0 {_peak()}"))
    big = make_workload(seed, big_mules, n_steps, cnn)
    quarter = big_mules // n_chips
    for method in ("mlmule", "gossip"):
        final, secs = _distributed_replay(big, wide, method)
        placed = []
        for leaf in jax.tree.leaves(final["mule_models"]):
            shards = leaf.addressable_shards
            placed.append(len({s.device for s in shards}) == n_chips
                          and all(s.data.shape[0] == quarter
                                  for s in shards))
        finite = all(bool(jnp.isfinite(l).all())
                     for l in jax.tree.leaves(final["mule_models"]))
        checks.append(Check(
            "4", all(placed) and finite,
            f"{method} run_population_distributed M={big_mules} T={n_steps} "
            f"on a 1x{n_chips} mesh: finite; every mule leaf in "
            f"{n_chips} shards of {quarter} mules, one per chip: "
            f"{all(placed)} | smoke figures: {secs:.1f} s (compile "
            f"included), process peak on chip 0 {_peak()}"))
        del final
    return checks


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


class _CacheEvents:
    """Counts persistent compilation cache reads and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self)

    def __call__(self, event: str, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _run(phases) -> List[Check]:
    checks = []
    for name, fn in phases:
        try:
            out = fn()
        except Exception as e:  # a failed phase is reported, not fatal
            out = Check(name, False, f"raised {type(e).__name__}: {e}")
        for c in (out if isinstance(out, list) else [out]):
            print(c.line(), flush=True)
            checks.append(c)
    return checks


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a four-chip host")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports {jax.devices()}); "
              "this script only runs on a TPU", file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(jax.devices()) < n_chips:
        print(f"chip_smoke: --four-chips needs 4 TPU chips, found "
              f"{jax.devices()}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    events = _CacheEvents()
    print(f"device: {dev.device_kind} x {len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        checks = _run([("4", lambda: phase_four_chips(args.seed, STEPS))])
    else:
        w = make_workload(args.seed, MULES, STEPS)
        held = {}

        def a():
            check, held["a"] = phase_mlmule(w)
            return check

        def d():
            from repro.baselines.gossip import flatten_population
            flat, _ = flatten_population(jax.device_put(held["a"]))
            checks = phase_kernels(flat, args.seed)
            del flat
            ragged = jax.random.normal(jax.random.PRNGKey(args.seed + 3),
                                       (RAGGED_MULES, _n_params(held["a"])))
            return checks + phase_kernels(ragged, args.seed)

        checks = _run([
            ("a", a),
            ("b", lambda: phase_streamed(w, held["a"])),
            ("c", lambda: phase_gossip(w, held["a"])),
            ("d", d),
            ("e", lambda: phase_cpu_parity(args.seed)),
        ])
    print(f"compile cache: {events.hits} programs read from {cache_dir}, "
          f"{events.misses} compiled (smoke run {time.perf_counter() - t0:.0f}"
          " s)", flush=True)
    if not checks or not all(c.ok for c in checks):
        print(f"chip_smoke: {sum(not c.ok for c in checks)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
