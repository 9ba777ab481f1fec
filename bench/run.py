"""Chip benchmark of the ML Mule population replay.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted`` (population steps in the window), ``failed``,
``metrics``, ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: every number that decided ``correct`` with its limit (also the
last lines of standard error).

One run:
1. refuse to run without a TPU, or with fewer chips than the cell asks;
2. keep JAX's persistent compilation cache in ``.jax_cache/`` of the
   checkout;
3. make the dataset, pools, weights and the program's commuter generator
   from ``--seed``; check that the generator's per-mule draws equal the
   benchmark's own (``schedule.py``);
4. drive the engine's ``run_population_streamed`` from those weights for
   one chunk (this compiles, or loads from the cache) and keep its state
   on the host for the comparison; on a mesh, one call of two chunks
   more; then one more chunk, timed;
5. size the window to about ``--seconds`` in whole chunks, and check that
   the generator expands the same rows as the benchmark's copy for every
   step driven;
6. ``setup_s`` ends here. The window is one call of the entry point, on the
   state the warm-up left, from dispatch to ``block_until_ready`` on the
   whole final state. A compile or a trace inside it fails the run;
7. read the peak memory, check the window's final state (finite models;
   the step counter, and the spaces' receipt counts and age rings over
   every step driven), free the program's state, replay the first chunk
   with the plain reference (``reference.py``) and judge the comparison
   (``compare.py``) against ``bench/limits/<cell>.json``.

With ``--trace 1`` the window runs under the profiler, and the per-layer
metrics are read from that trace by ``bench/metrics/<metric>.py``: each
reader's ``read(ctx)`` returns its value, or None where it finds nothing to
read. ``ctx`` holds ``events`` (``devtrace.load``'s), ``summary``
(``devtrace.summarize``'s), ``layers`` (``bench/layers.py``'s split by the
program's scopes, in ms per step), ``steps`` (the window's), ``trained``
(per step, the mules whose training it keeps, by ``schedule.py``),
``required_flops`` (``work.py``'s count over those), ``peak`` (the chip's,
``peaks.py``) and ``cell`` (``cell.reference`` is the configuration's
reference file, where its own counts live).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))


class NoChip(RuntimeError):
    pass


class Compiles:
    """Counts compiles and traces through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _check_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {devs}")
    return devs[:chips]


def _memory_peak(devices) -> Dict[str, int]:
    stats = [d.memory_stats() or {} for d in devices]
    return {"peak_bytes_in_use": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats),
            "bytes_limit": max(int(s.get("bytes_limit", 0)) for s in stats)}


def run(argv: Optional[List[str]] = None, log=print) -> Dict[str, Any]:
    """One run; returns the result line's object."""
    args = _parse(argv)
    import spec
    cell = spec.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    import jax.numpy as jnp
    import numpy as np
    devices = _check_chips(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = Compiles()

    import compare
    import program as prog_mod
    import schedule
    import work
    from peaks import peak

    ref = cell.reference
    tr = cell.traffic
    chunk = tr["chunk_len"]

    with jax.profiler.TraceAnnotation("bench/setup"):
        inputs = prog_mod.make_inputs(cell, ref, args.seed, devices[0])
        mule0, fixed0 = prog_mod.weights(cell, ref, inputs, devices[0])
        prog = prog_mod.make_program(cell, inputs)
        draws = schedule.commuter_draws(inputs.seeds["schedule"],
                                        tr["mules"], tr["mobility"])
        gen_draws = {k: np.asarray(v) for k, v in
                     prog.generator.arrays().items()}
        for k in ("home", "work", "phase", "stride", "aphase"):
            if k in draws and not np.array_equal(draws[k], gen_draws.get(k)):
                raise RuntimeError(f"the program's commuter draws differ "
                                   f"from the benchmark's in {k!r}")
        state = prog.initial_state(mule0, fixed0)
        del mule0, fixed0
        keys = [jax.random.fold_in(inputs.key, i) for i in range(3)]

    with jax.profiler.TraceAnnotation("bench/warmup"):
        state, _ = prog.replay(state, inputs, keys[0], chunk)
        jax.block_until_ready(state)
        first = jax.device_get(state)
        calls = [chunk]
        if prog.mesh is not None:
            # on a mesh, every chunk after a call's first takes the sharded
            # ``last_fid`` the chunk before it left: another program
            state, _ = prog.replay(state, inputs, keys[1], 2 * chunk)
            jax.block_until_ready(state)
            calls.append(2 * chunk)
        t0 = time.perf_counter()
        state, _ = prog.replay(state, inputs, keys[1], chunk)
        jax.block_until_ready(state)
        chunk_s = time.perf_counter() - t0
        calls.append(chunk)

    n_chunks = max(2, int(round(args.seconds / max(chunk_s, 1e-6))))
    n_steps = n_chunks * chunk
    expand = jax.jit(prog.generator.expand, static_argnums=(3,))
    with jax.profiler.TraceAnnotation("bench/schedule_check"):
        for t0_ in range(0, n_steps, chunk):
            got = expand(prog.generator.arrays(), None,
                         jnp.asarray(t0_, jnp.int32), chunk)
            want = schedule.commuter_rows(draws, tr["mobility"], t0_, chunk)
            for k in ("fixed_id", "exchange", "active"):
                if not np.array_equal(np.asarray(got[k]), want[k]):
                    raise RuntimeError(
                        f"the program's schedule differs from the "
                        f"benchmark's in {k!r} at steps {t0_}..{t0_ + chunk}")
    trained = schedule.trained_per_step(
        tr["method"], schedule.commuter_rows(draws, tr["mobility"], 0,
                                             n_steps), 0)
    setup_s = time.perf_counter() - T_START
    from repro.scenarios import jit_cache_stats
    before = (counter.compiles, counter.traces,
              jit_cache_stats()["traces"])

    trace_dir = os.path.join(TRACE_DIR, f"{cell.name}-{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench/window"):
        t0 = time.perf_counter()
        final, aux = prog.replay(state, inputs, keys[2], n_steps)
        jax.block_until_ready(final)
        window_s = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
    in_window = (counter.compiles - before[0], counter.traces - before[1],
                 jit_cache_stats()["traces"] - before[2])

    mem = _memory_peak(devices)
    compiled = prog.compiled_chunk(final, aux["last_fid"], inputs, keys[2])
    ma = compiled.memory_analysis()
    mem["chunk_program_bytes"] = int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    hlo_text = compiled.as_text() if args.trace else ""
    del compiled, aux
    calls.append(n_steps)
    method = tr["method"]["name"]
    from reference import Population, freshness_over_calls
    numbers = compare.window_numbers(
        final, method, sum(calls),
        freshness_over_calls(tr, draws, calls) if method == "mlmule"
        else None)
    numbers["window_compiles"] = float(sum(in_window))
    del final, state

    # the reference, once the program's state is gone
    mule0, fixed0 = prog_mod.weights(cell, ref, inputs, devices[0])
    pop = Population(cell, ref, inputs.context, keys[0], draws,
                     block=tr.get("reference_block", 128))
    with jax.default_device(devices[0]):
        r_mule, r_fixed, r_fresh = pop.run(mule0, fixed0, chunk)
        first = jax.device_put(first, devices[0])
        numbers.update(compare.state_numbers(
            tr["method"]["name"], first, r_mule, r_fixed,
            r_fresh, mule0, fixed0))
    correct, lines = compare.judge(numbers, cell.limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(mem["peak_bytes_in_use"],
                                       mem["chunk_program_bytes"])}
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": n_steps,
        "failed": sum(not (l["value"] is not None and l["limit"] is not None
                           and l["value"] <= l["limit"]) for l in lines)}
    if not args.trace:
        out["metrics"] = {
            "steps_per_s": {"value": n_steps / window_s, "unit": "steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        import devtrace as tr_mod
        import layers
        events = tr_mod.load(tr_mod.find_xplane(trace_dir),
                             tr_mod.hlo_scopes(hlo_text))
        summary = tr_mod.summarize(events, n_steps)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"summary": summary, "cell": cell,
               "peak": peak(dev.device_kind),
               "required_flops": float(sum(work.step_train_flops(
                   cell.config, int(k), ref) for k in trained)),
               "events": events, "steps": n_steps, "trained": trained,
               "layers": layers.per_step(layers.split(events), n_steps)}
        metrics = {}
        for m in cell.per_layer:
            reader = spec.load_module(os.path.join(BENCH_DIR, "metrics",
                                                   m["name"] + ".py"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = summary.get("busy_s", 0.0)
        device["window_s"] = summary.get("window_s", 0.0)
        out["breakdown"] = {"device_ops": summary.get("top_ops", []),
                            "idle_gaps": summary.get("idle_gaps", [])}
    out["device"] = device
    out["checks"] = {l["name"]: {"value": l["value"], "limit": l["limit"]}
                     for l in lines}
    log(json.dumps({"info": {"chunk_s": chunk_s, "window_s": window_s,
                             "n_steps": n_steps,
                             "trained_mules": int(sum(trained)),
                             "memory": mem, "in_window": in_window}}),
        file=sys.stderr)
    for s in compare.describe(lines):
        log(s, file=sys.stderr)
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
