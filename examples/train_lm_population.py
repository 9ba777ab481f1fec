"""End-to-end driver: ML Mule over a population of language models.

This is the framework's "big model" path: each fixed device hosts a
transformer LM (selectable with --arch from the 10 assigned architectures,
reduced config on CPU) trained on space-specific token streams; mules carry
LM snapshots between spaces. Demonstrates that the protocol layer is
model-agnostic — the same population engine that moves CNNs moves sharded
transformer pytrees.

  PYTHONPATH=src python examples/train_lm_population.py --arch stablelm-1.6b \
      --steps 60
(full-scale: drop --smoke-implied reduced config by editing ARCH below and
run under the production mesh via repro.launch.train)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import PopulationConfig, init_population, population_step
from repro.core.freshness import FreshnessConfig
from repro.data import make_lm_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.mobility import MobilityConfig, init_mobility, mobility_step
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--n-fixed", type=int, default=4)
    ap.add_argument("--n-mules", type=int, default=6)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    print(f"population of {args.n_fixed} fixed + {args.n_mules} mule "
          f"{cfg.name} models ({cfg.param_count()/1e6:.2f}M params each)")

    seqs, spaces = make_lm_dataset(0, n_seqs=args.n_fixed * 32,
                                   seq_len=args.seq, vocab=cfg.vocab,
                                   n_spaces=args.n_fixed)
    per_space = [seqs[spaces == f] for f in range(args.n_fixed)]
    n = min(len(p) for p in per_space)
    data = jnp.asarray(np.stack([p[:n] for p in per_space]))  # [F, n, S]

    def train_fn(params, batch, key):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, {"tokens": batch})
        return jax.tree.map(lambda p, g: p - 3e-3 * g, params, grads)

    pcfg = PopulationConfig(mode="fixed", n_fixed=args.n_fixed,
                            n_mules=args.n_mules,
                            freshness=FreshnessConfig())
    pop = init_population(jax.random.PRNGKey(0), model.init, pcfg)
    mcfg = MobilityConfig(n_mules=args.n_mules, n_areas=1, p_cross=0.2)
    mob = init_mobility(jax.random.PRNGKey(1), mcfg)

    @jax.jit
    def eval_loss(params, toks):
        return model.loss(params, {"tokens": toks})[0]

    @jax.jit
    def sim_step(pop, mob, key):
        mob, info = mobility_step(mob, mcfg)
        kb, kt = jax.random.split(key)
        idx = jax.random.randint(kb, (args.n_fixed, args.batch), 0, n)
        batches = {"fixed": jnp.take_along_axis(
            data, idx[:, :, None], axis=1), "mule": None}
        info = {"fixed_id": jnp.clip(info["fixed_id"], -1, args.n_fixed - 1),
                "exchange": info["exchange"]}
        return population_step(pop, info, batches, train_fn, pcfg, kt), mob

    key = jax.random.PRNGKey(42)
    t0 = time.time()
    for t in range(args.steps):
        key, k = jax.random.split(key)
        pop, mob = sim_step(pop, mob, k)
        if (t + 1) % 20 == 0:
            losses = [float(eval_loss(
                jax.tree.map(lambda l, f=f: l[f], pop["fixed_models"]),
                data[f, :args.batch])) for f in range(args.n_fixed)]
            print(f"step {t+1:4d}  per-space LM loss: "
                  f"{np.round(losses, 3)}  ({time.time()-t0:.0f}s)")
    print("done")


if __name__ == "__main__":
    main()
