"""Quickstart: a 60-second ML Mule simulation.

Eight smart-space fixed devices, twelve phone "mules", the paper's CNN on a
procedural image task. Watch per-space accuracy improve as mules ferry model
snapshots between spaces — no server, no always-on connectivity.

  PYTHONPATH=src python examples/quickstart.py

Scenarios
---------
Mobility, protocol mode, and data partition are bundled behind string names
in the scenario registry; the whole run is one compiled ``lax.scan``:

    from repro.scenarios import SCENARIOS, get_scenario, run_population

    spec = get_scenario("random_walk")      # or: commuter, foursquare_sparse,
                                            #     shift_worker, event_crowd
    co = spec.colocation(seed=1, n_mules=12, n_steps=240)
    final, aux = run_population(pop, co, batch_fn, train_fn, pcfg, key,
                                eval_every=60, eval_fn=eval_hook)

New workloads are one ``repro.scenarios.register(...)`` entry, and
``examples/run_scenario.py --scenario <name>`` replays any of them
end-to-end against the paper's harness.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mule_cnn import CNNConfig
from repro.core import PopulationConfig, init_population
from repro.data import dirichlet_partition, make_image_dataset
from repro.data.partition import train_test_split
from repro.models.cnn import accuracy, cnn_forward, init_cnn, xent_loss
from repro.launch.compile_cache import use_compile_cache
from repro.scenarios import get_scenario, run_population

use_compile_cache()

F, M, STEPS = 8, 12, 240

# --- data: 20 super-classes, Dirichlet(0.01) over 8 spaces ------------------
x, sup, _ = make_image_dataset(0, n_per_sub=16, n_super=20, size=16, noise=3.0)
parts = dirichlet_partition(sup, F, alpha=0.01, seed=0, min_per_part=24)
rng = np.random.default_rng(0)
tr, te = zip(*[train_test_split(p, 0.2, 0) for p in parts])
n_tr = min(32, min(len(t) for t in tr))
n_te = min(len(t) for t in te)
Xtr = jnp.asarray(np.stack([x[t[:n_tr]] for t in tr]))
Ytr = jnp.asarray(np.stack([sup[t[:n_tr]] for t in tr]))
Xte = jnp.asarray(np.stack([x[t[:n_te]] for t in te]))
Yte = jnp.asarray(np.stack([sup[t[:n_te]] for t in te]))

# --- model + protocol ---------------------------------------------------------
mc = CNNConfig(image_size=16, conv_features=(8, 16), hidden=64, n_classes=20)


def train_fn(params, batch, key):
    xb, yb = batch
    g = jax.grad(lambda p: xent_loss(cnn_forward(p, xb), yb))(params)
    return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)


def batch_fn(key, t):
    idx = jax.random.randint(key, (F, 16), 0, Xtr.shape[1])
    return {"fixed": (jnp.take_along_axis(Xtr, idx[:, :, None, None, None], 1),
                      jnp.take_along_axis(Ytr, idx, 1)), "mule": None}


pcfg = PopulationConfig(mode="fixed", n_fixed=F, n_mules=M)
pop = init_population(jax.random.PRNGKey(0), lambda k: init_cnn(k, mc), pcfg)

# --- one compiled scan over the whole scenario --------------------------------
co = get_scenario("random_walk").colocation(1, M, STEPS)
eval_v = jax.vmap(lambda p, xd, yd: accuracy(cnn_forward(p, xd), yd))
pop, aux = run_population(
    pop, co, batch_fn, train_fn, pcfg, jax.random.PRNGKey(42),
    eval_every=60, eval_fn=lambda st, last: eval_v(st["fixed_models"], Xte, Yte))

for t, acc in zip(aux["eval_steps"], np.asarray(aux["evals"])):
    print(f"step {t+1:4d}  per-space acc: {np.round(acc, 2)}  "
          f"mean {acc.mean():.3f}")
print("done — models evolved purely through mule-carried snapshots.")
