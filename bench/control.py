"""Readings that set the limits of ``bench/limits/<cell>.json``.

  python3 bench/control.py --workload <cell> --seeds 12 [--control-seeds 3]

On the chip, at the cell's own size, for each seed: the program's first
chunk, exactly as a run drives it (same entry point, weights, data, batch
draws and key), against the float32 reference at ``highest``; and, on the
first ``--control-seeds`` seeds, these stand-ins put in the program's place
against the same reference:

- ``control``: the reference computed in bfloat16, the nearest precision
  below the configuration's float32 (weights, data and arithmetic);
- ``half_batch``: the reference whose SGD step takes the mean over half of
  each batch and leaves the rest out;
- ``no_exchange`` (a cell on a mule mesh): the program with the exchange
  between chips left out (``ordered_psum`` returns each chip's own part).

A state left unchanged reads 1 on every ``*_change_gap`` by construction
and is not run. Prints one JSON line per seed and reading; the benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))


def unmoved(p, r, w) -> float:
    """Share of the elements the reference moved off their initial value
    that the program left exactly where they were."""
    import jax
    import numpy as np
    moved = kept = 0
    for a, b, c in zip(*(jax.tree.leaves(jax.device_get(x))
                         for x in (p, r, w))):
        ref_moved = np.asarray(b) != np.asarray(c)
        moved += int(ref_moved.sum())
        kept += int((ref_moved & (np.asarray(a) == np.asarray(c))).sum())
    return kept / max(moved, 1)


def worst_leaves(prefix: str, p, r, w):
    """Which leaf reads worst in each model number (its path in the
    stacked population), by ``compare.model_numbers``' rule."""
    import jax
    import numpy as np
    import compare
    norms, _ = jax.tree_util.tree_flatten_with_path(
        compare._leaf_norms(p, r, w))
    rows = np.stack([np.asarray(v, np.float64) for _, v in norms])
    d_p, d_r, diff = rows[:, 0], rows[:, 1], rows[:, 2]
    med = float(np.median(d_r))
    den = np.where(d_r >= 1e-3 * med, np.maximum(d_r, med), np.inf)
    name = lambda i: jax.tree_util.keystr(norms[int(i)][0])
    return {f"{prefix}_change_gap_leaf": name(np.argmax(np.abs(d_p - d_r)
                                                        / den)),
            f"{prefix}_diff_leaf": name(np.argmax(diff / den))}


def readings(cell, seed: int, stand_ins: bool, program_precision=None):
    """Yields (kind, numbers) for one seed. ``program_precision`` runs the
    program's chunk inside ``jax.default_matmul_precision`` (a witness:
    the same program with its contractions in float32)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import compare
    import program as prog_mod
    import schedule
    from reference import Population, loss_of

    devices = jax.devices()[:cell.chips]
    tr = cell.traffic
    ref = cell.reference
    inputs = prog_mod.make_inputs(cell, ref, seed, devices[0])
    mule0, fixed0 = prog_mod.weights(cell, ref, inputs, devices[0])
    prog = prog_mod.make_program(cell, inputs)
    key0 = jax.random.fold_in(inputs.key, 0)
    scope = (jax.default_matmul_precision(program_precision)
             if program_precision else contextlib.nullcontext())
    with scope:
        state, _ = prog.replay(prog.initial_state(mule0, fixed0), inputs,
                               key0, tr["chunk_len"])
    first = jax.device_get(state)
    del state, mule0, fixed0
    draws = schedule.commuter_draws(inputs.seeds["schedule"], tr["mules"],
                                    tr["mobility"])
    mule0, fixed0 = prog_mod.weights(cell, ref, inputs, devices[0])
    block = tr.get("reference_block", 128)

    def replay(**kw):
        pop = Population(cell, ref, inputs.context, key0, draws,
                         block=block, **kw)
        return pop.run(mule0, fixed0, tr["chunk_len"])

    method = tr["method"]["name"]
    with jax.default_device(devices[0]):
        r_mule, r_fixed, r_fresh = replay()
        as_state = lambda m, f, fr: {"mule_models": m, "fixed_models": f,
                                     "fresh": fr}
        first = jax.device_put(first, devices[0])
        numbers = compare.state_numbers(method, first, r_mule, r_fixed,
                                        r_fresh, mule0, fixed0)
        numbers["mule_unmoved"] = unmoved(first["mule_models"], r_mule, mule0)
        numbers["fixed_unmoved"] = unmoved(first["fixed_models"], r_fixed,
                                           fixed0)
        numbers.update(worst_leaves("mule", first["mule_models"], r_mule,
                                    mule0))
        if method == "mlmule":
            numbers.update(worst_leaves("fixed", first["fixed_models"],
                                        r_fixed, fixed0))
        yield "program" + (f"@{program_precision}" if program_precision
                           else ""), numbers
        del first
        if not stand_ins:
            return
        c_mule, c_fixed, c_fresh = replay(dtype=jnp.bfloat16,
                                          precision=None)
        yield "control", compare.state_numbers(
            method, as_state(c_mule, c_fixed, c_fresh), r_mule, r_fixed,
            r_fresh, mule0, fixed0)
        del c_mule, c_fixed
        lr, half = cell.config["lr"], cell.config["batch"] // 2
        hi, loss = jax.lax.Precision.HIGHEST, loss_of(ref)

        def half_sgd(p, x, y, *shared):
            g = jax.grad(lambda q: loss(ref.forward(q, x[:half], hi, *shared),
                                        y[:half]))(p)
            return jax.tree.map(lambda a, b: a - lr * b, p, g)

        h_mule, h_fixed, h_fresh = replay(train=half_sgd)
        yield "half_batch", compare.state_numbers(
            method, as_state(h_mule, h_fixed, h_fresh), r_mule, r_fixed,
            r_fresh, mule0, fixed0)
        del h_mule, h_fixed
        if prog.mesh is not None:
            yield "no_exchange", compare.state_numbers(
                method, _without_exchange(prog, inputs, key0, cell, ref),
                r_mule, r_fixed, r_fresh, mule0, fixed0)


def _without_exchange(prog, inputs, key, cell, ref):
    """The program's first chunk with ``ordered_psum`` returning each
    chip's own part: the exchange between chips left out."""
    import jax
    import program as prog_mod
    import repro.core.distributed as dist
    from repro.scenarios import jit_cache_clear
    real = dist.ordered_psum
    dist.ordered_psum = lambda x, axis_name: x
    jit_cache_clear()
    try:
        mule0, fixed0 = prog_mod.weights(cell, ref, inputs)
        state, _ = prog.replay(prog.initial_state(mule0, fixed0), inputs,
                               key, cell.traffic["chunk_len"])
        return jax.device_put(jax.device_get(state), jax.devices()[0])
    finally:
        dist.ordered_psum = real
        jit_cache_clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--program-precision", default=None,
                    help="run the program's chunk at this matmul precision "
                         "(a witness; the benchmark runs the default)")
    args = ap.parse_args(argv)
    import jax
    from spec import load_cell
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    cell = load_cell(args.workload)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        for kind, numbers in readings(cell, seed, i < args.control_seeds,
                                      program_precision=args.program_precision):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
