"""Mobile-device training scenario (paper Fig. 2b) on IMU HAR.

Phones collect accelerometer/gyro windows as their users move through
spaces; fixed devices only host/aggregate. Compares ML Mule vs Gossip vs
Local over time (Fig. 8/9 analogue).

  PYTHONPATH=src python examples/har_mobile_training.py [--p-cross 0.1]
"""
import argparse

from benchmarks.common import ExperimentConfig, run_experiment
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--p-cross", default="0.1")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    print(f"HAR (LSTM-CNN over IMU windows), P_cross={args.p_cross}")
    for method in ("local", "gossip", "mlmule"):
        cfg = ExperimentConfig(task="har", mode="mobile", method=method,
                               pattern=args.p_cross, steps=args.steps,
                               seed=args.seed, batch=12, lr=0.03)
        r = run_experiment(cfg)
        trace = " ".join(f"{t}:{a:.2f}" for t, a in r["trace"])
        print(f"{method:8s} final={r['pre_local_acc']:.3f}  trace: {trace}")


if __name__ == "__main__":
    main()
