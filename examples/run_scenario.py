"""Run any registered scenario end-to-end through the paper's harness.

  PYTHONPATH=src python examples/run_scenario.py --scenario commuter
  PYTHONPATH=src python examples/run_scenario.py --scenario commuter \
      --method gossip --seeds 4          # seed-averaged, one vmapped program
  PYTHONPATH=src python examples/run_scenario.py --scenario commuter \
      --method oppcl --distributed       # mule-sharded over host devices
  PYTHONPATH=src python examples/run_scenario.py --list

The scenario supplies mobility, protocol mode, data partition — and, for
the churn family, a per-step device activity mask the engine threads
through every path: ``commuter_churn`` (Markov join/leave sessions),
``event_crowd_flash`` (flash joins, mass exits), ``multi_area_3city``
(3 near-isolated cities, 12 spaces), ``mixed_cadence`` (per-space
exchange tempos); the ``har_*`` variants bind the LSTM-CNN IMU task. The
harness supplies the model, pretraining, and the compiled scan engine.
Every mobile method (mlmule/gossip/oppcl/local/mlmule+gossip) rides the
engine; with ``--seeds N > 1`` the replay batches all seeds into one
vmapped compiled program (``run_sweep_experiment``); with ``--distributed``
it shards the mule population over a forced host-device mesh instead
(``run_population_distributed`` — one shard_map'd scan, the peer-encounter
baselines ring their neighbor search across shards); with ``--stream`` the
colocation schedule is generated chunk-by-chunk inside the compiled replay
(``run_population_streamed`` — O(chunk*M) schedule memory instead of
O(T*M), bitwise-identical results, composes with ``--distributed``); with
``--processes N`` the whole run re-execs as an N-rank local
``jax.distributed`` cluster on the host CPU (gloo collectives) and the
mule mesh spans every rank's devices — same engines, same results,
per-process state. It rehearses a multi-host run on the CPU, so it asks
the caller to say so with ``JAX_PLATFORMS=cpu``::

  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/run_scenario.py \\
      --scenario commuter --distributed --stream --processes 2 \\
      --devices-per-process 4
"""
import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)                       # for `benchmarks`
sys.path.insert(0, os.path.join(_ROOT, "src"))  # for `repro`

def _argv_value(flag, default):
    return (sys.argv[sys.argv.index(flag) + 1] if flag in sys.argv
            else default)


# multi-process lane: `--processes N` re-execs this script as an
# N-process local `jax.distributed` cluster. Decided by argv peek for the
# same reason as the device forcing below — the spawn must happen before
# anything imports jax — and skipped inside the spawned children, which
# carry the REPRO_MP_* coordinator triple in their environment instead.
from repro.launch.multiprocess import (initialize_from_env,  # noqa: E402
                                       spawn_local_cluster)

_N_PROC = int(_argv_value("--processes", "1"))
if _N_PROC > 1 and not os.environ.get("REPRO_MP_COORDINATOR"):
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("--processes runs every rank on the host CPU (it rehearses "
                 "a multi-host run); set JAX_PLATFORMS=cpu to run it")
    n_dev = int(_argv_value("--devices-per-process", "4"))
    results = spawn_local_cluster(
        [sys.executable] + sys.argv, _N_PROC, n_dev,
        coordinator=_argv_value("--coordinator", None))
    sys.stdout.write(results[0].stdout)
    for pid, res in enumerate(results):
        if res.returncode != 0:
            sys.stderr.write(f"--- rank {pid} failed "
                             f"(exit {res.returncode}) ---\n{res.stdout}\n")
            sys.exit(res.returncode)
    sys.exit(0)

# the host-device mesh must be forced before jax initializes, so peek at
# argv ahead of the real argparse run (which needs jax-importing modules)
if "--distributed" in sys.argv and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

# inside a spawned rank: bring up jax.distributed before the first jax
# import below initializes the backend (no-op without the env triple)
initialize_from_env()

from benchmarks.common import (METHODS_MOBILE, ExperimentConfig,
                               run_experiment, run_sweep_experiment)
from repro.launch.compile_cache import use_compile_cache
from repro.scenarios import SCENARIOS, list_scenarios


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="random_walk",
                    choices=list_scenarios(),
                    help="registered scenario; churn variants "
                         "(commuter_churn, event_crowd_flash) replay with "
                         "device join/leave masks, multi_area_3city spans "
                         "3 cities, mixed_cadence varies per-space "
                         "exchange tempo, har_* bind the LSTM-CNN IMU task "
                         "(see --list)")
    ap.add_argument("--method", default="mlmule", choices=METHODS_MOBILE)
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--n-mules", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=1,
                    help="sweep seed..seed+N-1 as one vmapped program")
    ap.add_argument("--distributed", action="store_true",
                    help="shard the mule population over the available "
                         "devices (on CPU hosts, 8 forced host devices; "
                         "the run prints the mesh it settles on — "
                         "n-mules must divide the shard count) and "
                         "replay on the distributed scan engine — every "
                         "method (mlmule, gossip, oppcl, local, "
                         "mlmule+gossip) now shards; mobile-mode runs "
                         "report final accuracy only (in-scan eval reads "
                         "sharded state). Mutually exclusive with "
                         "--seeds > 1.")
    ap.add_argument("--stream", action="store_true",
                    help="generate the colocation schedule chunk-by-chunk "
                         "inside the compiled replay instead of "
                         "materializing the full [T, M] tensors up front — "
                         "O(chunk*M) schedule memory instead of O(T*M), "
                         "bitwise-identical results (run_population_"
                         "streamed; composes with --distributed). "
                         "Mutually exclusive with --seeds > 1.")
    ap.add_argument("--stream-chunk", type=int, default=0,
                    help="chunk length for --stream (0 = engine default; "
                         "must be a multiple of the eval cadence)")
    ap.add_argument("--rebucket-every", type=int, default=0,
                    help="distributed runs: re-check the shard/area "
                         "alignment every N steps and re-bucket the mule "
                         "population when the drift fraction crosses "
                         "--rebucket-threshold (0 = off; must be a "
                         "multiple of --stream-chunk so swaps land on "
                         "chunk boundaries). Keeps the ring's hop pruning "
                         "effective on migratory scenarios "
                         "(multi_area_migratory).")
    ap.add_argument("--rebucket-threshold", type=float, default=0.25,
                    help="drifted-mule fraction that triggers a re-bucket "
                         "swap (see --rebucket-every)")
    ap.add_argument("--processes", type=int, default=1,
                    help="rehearse a multi-host run on the CPU: re-exec "
                         "this run as an N-process local jax.distributed "
                         "cluster of host-CPU ranks (requires --distributed "
                         "and JAX_PLATFORMS=cpu; the mule mesh then spans "
                         "every process's devices and n-mules must divide "
                         "processes x devices-per-process; composes with "
                         "--stream and --rebucket-every)")
    ap.add_argument("--devices-per-process", type=int, default=4,
                    help="forced host devices per rank for --processes")
    ap.add_argument("--coordinator", default=None, metavar="ADDR",
                    help="host:port for the jax.distributed coordinator "
                         "(default: a free local port)")
    ap.add_argument("--list", action="store_true",
                    help="print the registry and exit")
    args = ap.parse_args()

    if args.list:
        for name in list_scenarios():
            print(f"{name:18s} {SCENARIOS[name].description}")
        return
    use_compile_cache()

    if args.processes > 1 and not args.distributed:
        ap.error("--processes shards the population across a cluster; "
                 "add --distributed")
    if args.distributed and args.seeds > 1:
        ap.error("--distributed runs one seed; drop --seeds")
    if args.stream and args.seeds > 1:
        ap.error("--stream runs one seed; drop --seeds")
    if args.rebucket_every:
        if not args.distributed:
            ap.error("--rebucket-every re-buckets the sharded population; "
                     "add --distributed")
        if args.stream_chunk and args.rebucket_every % args.stream_chunk:
            # validated here, before any device work: a misaligned cadence
            # would otherwise only surface once the engine builds chunks
            raise ValueError(
                f"--rebucket-every={args.rebucket_every} must be a "
                f"multiple of --stream-chunk={args.stream_chunk} so "
                "re-bucketing lands on chunk boundaries")

    spec = SCENARIOS[args.scenario]
    print(f"scenario={spec.name} mode={spec.mode} dist={spec.dist} "
          f"task={spec.task} method={args.method}"
          + (" [distributed]" if args.distributed else "")
          + (" [streamed]" if args.stream else "")
          + (f" [{args.processes} processes]" if args.processes > 1
             else ""))
    cfg = ExperimentConfig(scenario=args.scenario, method=args.method,
                           steps=args.steps, n_mules=args.n_mules,
                           seed=args.seed, distributed=args.distributed,
                           stream=args.stream,
                           stream_chunk=args.stream_chunk,
                           rebucket_every=args.rebucket_every,
                           rebucket_threshold=args.rebucket_threshold)

    if args.seeds > 1:
        seeds = range(args.seed, args.seed + args.seeds)
        r = run_sweep_experiment(cfg, seeds)
        d = r["methods"][args.method]
        import numpy as np
        spread = np.asarray(d["acc"]).std(axis=0)
        for t, acc, sd in zip(r["eval_steps"], d["mean_acc"], spread):
            print(f"  step {t+1:4d}  mean acc {acc:.3f} +/- {sd:.3f} "
                  f"({args.seeds} seeds)")
        print(f"final pre-local acc {d['mean_final_acc']:.3f}  "
              f"wall {r['wall_s']:.0f}s")
        return

    r = run_experiment(cfg)
    for t, acc in r["trace"]:
        print(f"  step {t+1:4d}  mean acc {acc:.3f}")
    print(f"final pre-local acc {r['pre_local_acc']:.3f}  "
          f"wall {r['wall_s']:.0f}s")


if __name__ == "__main__":
    main()
