"""Fixed-device training scenario (paper Fig. 2a): smart-space devices hold
the data and train; mules ferry snapshots. Compares ML Mule against
Local-Only and FedAvg on the same partition and prints the Table-1-style
pre/post-local accuracies.

ML Mule runs through the compiled scan engine (``repro.scenarios``); the
baselines drive the same precomputed co-location tensors step by step.

  PYTHONPATH=src python examples/smart_space_fixed_training.py \
      [--dist dir0.01] [--pattern 0.1] [--steps 240] [--scenario random_walk]
"""
import argparse

from benchmarks.common import ExperimentConfig, run_experiment
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", default="dir0.01")
    ap.add_argument("--pattern", default="0.1")
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", default="",
                    help="registry scenario name (overrides dist/pattern)")
    args = ap.parse_args()
    use_compile_cache()

    print(f"distribution={args.dist} mobility P_cross={args.pattern}"
          + (f" scenario={args.scenario}" if args.scenario else ""))
    print(f"{'method':10s} {'pre-local':>10s} {'post-local':>11s} {'wall':>7s}")
    for method in ("local", "fedavg", "mlmule"):
        cfg = ExperimentConfig(mode="fixed", method=method, dist=args.dist,
                               pattern=args.pattern, steps=args.steps,
                               seed=args.seed, scenario=args.scenario)
        r = run_experiment(cfg)
        print(f"{method:10s} {r['pre_local_acc']:10.3f} "
              f"{r['post_local_acc']:11.3f} {r['wall_s']:6.0f}s")


if __name__ == "__main__":
    main()
