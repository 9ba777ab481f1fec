"""The system under test, bound to a cell: the engine's streamed entry point
``run_population_streamed`` with the program's mule model, its population
config and its procedural commuter generator. This is the only file of the
benchmark that imports the program (``repro``), apart from the run's use of
``use_compile_cache``.

What the benchmark makes itself and hands in: the weights (``weights``),
the dataset and the mules' pools (``Inputs``), the batch draw and the SGD
step around the program's forward pass and loss.

Frozen shared weights: a reference file that defines ``init_shared(key,
cfg)`` has a base that every mule reads and none trains. It is built once,
on the device, into ``Inputs.context["shared"]``; ``init(key, cfg)`` gives
the trainable leaves only, which are all that the mules and the spaces hold,
exchange and carry. The program's forward is then ``forward(params, x,
shared)``, and the SGD step reads the base from the replay's context
(``bind_context``). A configuration without ``init_shared`` runs exactly
the code it ran before.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from spec import sub_seeds


def _resolve(dotted: str) -> Callable:
    mod, name = dotted.split(":")
    return getattr(importlib.import_module(mod), name)


@dataclasses.dataclass
class Inputs:
    """Everything one run feeds the program, made from ``--seed``."""
    seeds: Dict[str, int]
    context: Dict[str, Any]          # dataset + pools (+ the frozen base)
    key: Any                         # the replay's PRNG key


# ``weights`` splits its key in two (mules, spaces): the frozen base takes
# the next fold of the same key, so no existing draw changes
SHARED_FOLD = 2


def shared_key(seeds: Dict[str, int]):
    """The key of the frozen base, derived from the ``weights`` sub-seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seeds["weights"]),
                              SHARED_FOLD)


def make_inputs(cell, ref, seed: int, device=None) -> Inputs:
    """Dataset, per-mule pools and the replay key, on the device in one
    jitted call. The configuration's ``make_data`` returns ``n_classes``
    groups of ``per_class`` rows, ordered by group, in any dtype and with
    any label shape (a class per image, a next token per position). A
    mule's pool holds ``pool_images`` rows of the groups its home space
    sees (``classes_per_place`` consecutive groups). Where the reference
    file defines ``init_shared``, the frozen base is built in one more
    jitted call into ``context["shared"]``: once, not per mule or space."""
    from schedule import commuter_draws
    names = ("weights", "data", "schedule", "replay")
    seeds = dict(zip(names, sub_seeds(seed, len(names))))
    cfg, tr = cell.config, cell.traffic
    data = tr["data"]
    draws = commuter_draws(seeds["schedule"], tr["mules"], tr["mobility"])
    home = jnp.asarray(draws["home"], jnp.int32)

    def build(key, home):
        kd, kc, ki = jax.random.split(key, 3)
        x, y = ref.make_data(kd, cfg, data)
        n_cls, per = cfg["n_classes"], data["per_class"]
        m, pool = home.shape[0], cfg["pool_images"]
        cls = (home[:, None] * data["classes_per_place"]
               + jax.random.randint(kc, (m, pool), 0,
                                    data["classes_per_place"])) % n_cls
        pools = cls * per + jax.random.randint(ki, (m, pool), 0, per)
        return {"x": x, "y": y, "pools": pools.astype(jnp.int32)}

    with jax.default_device(device or jax.devices()[0]):
        context = jax.jit(build)(jax.random.PRNGKey(seeds["data"]), home)
        if hasattr(ref, "init_shared"):
            context["shared"] = jax.jit(
                lambda k: ref.init_shared(k, cfg))(shared_key(seeds))
        key = jax.random.PRNGKey(seeds["replay"])
    return Inputs(seeds, context, key)


def batch_index(key, pools, batch: int):
    """[M, batch] dataset rows a step trains on: ``batch`` draws with
    replacement from each mule's pool."""
    j = jax.random.randint(key, (pools.shape[0], batch), 0, pools.shape[1])
    return jnp.take_along_axis(pools, j, axis=1)


def weights(cell, ref, inputs: Inputs, device=None):
    """Mule and fixed-space weights from the seed, one jitted call: the
    trainable leaves (``ref.init``), never the frozen base."""
    tr = cell.traffic
    k = jax.random.PRNGKey(inputs.seeds["weights"])

    def build(k):
        km, kf = jax.random.split(k)
        init = lambda kk: ref.init(kk, cell.config)
        return (jax.vmap(init)(jax.random.split(km, tr["mules"])),
                jax.vmap(init)(jax.random.split(kf, tr["spaces"])))

    with jax.default_device(device or jax.devices()[0]):
        return jax.jit(build)(k)


def bind_context(train_fn: Callable, batch_fn: Callable):
    """The engine's ``(params, batch, key)`` train step and its batch draw
    for a train function that also reads the replay's context,
    ``train_fn(params, batch, key, context)``.

    The engine calls the batch draw with the context inside the compiled
    step, before that step's train step, in the same trace (``_scan_core``;
    under ``shard_map`` the context is replicated). The draw hands that
    traced context on, and the train step closes over it: the engine's
    ``vmap`` over mules sees it unbatched, so every mule reads the one copy
    that the replay takes as an argument. A train step traced without a
    draw before it raises."""
    seen = []

    def batch(key, t, ctx):
        seen[:] = [ctx]
        return batch_fn(key, t, ctx)

    def train(params, b, key):
        return train_fn(params, b, key, seen[0])

    return train, batch


@dataclasses.dataclass
class Program:
    pcfg: Any
    generator: Any
    train_fn: Callable               # (params, batch, key[, context])
    batch_fn: Callable
    method: str
    chunk_len: int
    mesh: Any = None                 # the mule mesh of a sharded cell
    dcfg: Any = None                 # its DistributedConfig
    with_context: bool = False       # train_fn takes the replay's context

    @functools.cached_property
    def engine_fns(self):
        """The train step and batch draw the engine is given: ``train_fn``
        and ``batch_fn`` themselves, or bound to the context. One pair per
        program, so the engine's cache of compiled replays hits."""
        if not self.with_context:
            return self.train_fn, self.batch_fn
        return bind_context(self.train_fn, self.batch_fn)

    def replay(self, state, inputs: Inputs, key, n_steps: int):
        """One call of the engine's streamed entry point."""
        from repro.scenarios import run_population_streamed
        train_fn, batch_fn = self.engine_fns
        return run_population_streamed(
            state, self.generator, batch_fn, train_fn, self.pcfg,
            key, n_steps=n_steps, chunk_len=self.chunk_len,
            method=self.method, context=inputs.context, donate=True,
            mesh=self.mesh, dcfg=self.dcfg)

    def compiled_chunk(self, state, last, inputs: Inputs, key):
        """The engine's compiled chunk program for these arguments: the one
        the window ran (lowered again, so compiled again or read from the
        cache), for its memory analysis and its HLO text."""
        from repro.scenarios.engine import get_compiled_chunk_replay
        train_fn, batch_fn = self.engine_fns
        gen_arrays = self.generator.arrays()
        fn = get_compiled_chunk_replay(
            state, self.generator, gen_arrays, batch_fn,
            inputs.context, key, train_fn, self.pcfg,
            method=self.method, eval_every=None, eval_fn=None,
            chunk_len=self.chunk_len, donate=True, mesh=self.mesh,
            dcfg=self.dcfg)
        return fn.lower(state, last, jnp.asarray(0, jnp.int32), gen_arrays,
                        None, inputs.context, key).compile()

    def initial_state(self, mule, fixed):
        """The engine's population state around the benchmark's weights;
        on a mesh, the distributed engine's state (its freshness variant),
        built straight into its shardings: mule leaves split over the data
        axis, the rest replicated."""
        from repro.core.freshness import init_freshness

        def build(mule, fixed):
            m = jax.tree.leaves(mule)[0].shape[0]
            state = {"mule_models": mule, "fixed_models": fixed,
                     "mule_ts": jnp.zeros((m,), jnp.float32),
                     "fresh": init_freshness(self.pcfg.n_fixed,
                                             self.pcfg.freshness),
                     "t": jnp.zeros((), jnp.float32)}
            if self.mesh is None:
                return state
            from repro.core.distributed import to_distributed_state
            return to_distributed_state(state, self.dcfg)

        if self.mesh is None:
            return build(mule, fixed)
        from jax.sharding import NamedSharding, PartitionSpec as P
        split = NamedSharding(self.mesh, P(self.dcfg.data_axis))
        whole = NamedSharding(self.mesh, P())
        shardings = {k: jax.tree.map(
            lambda _: split if k.startswith("mule") else whole, v)
            for k, v in jax.eval_shape(build, mule, fixed).items()}
        return jax.jit(build, out_shardings=shardings)(mule, fixed)


def make_program(cell, inputs: Inputs) -> Program:
    from repro.core import PopulationConfig
    from repro.core.freshness import FreshnessConfig
    from repro.mobility.streaming import commuter_stream
    cfg, tr = cell.config, cell.traffic
    fwd = _resolve(cfg["program"]["forward"])
    loss = _resolve(cfg["program"]["loss"])
    lr, batch = cfg["lr"], cfg["batch"]
    with_context = "shared" in inputs.context

    def sgd(params, b, key, *ctx):
        """With a frozen base, ``ctx`` is the replay's context; the gradient
        is of the trainable leaves only, the base a constant of the step."""
        xb, yb = b
        shared = [c["shared"] for c in ctx]
        g = jax.grad(lambda p: loss(fwd(p, xb, *shared), yb))(params)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)

    def batch_fn(key, t, ctx):
        idx = batch_index(key, ctx["pools"], batch)
        return {"fixed": None, "mule": (ctx["x"][idx], ctx["y"][idx])}

    f = tr["freshness"]
    pcfg = PopulationConfig(
        mode="mobile", n_fixed=tr["spaces"], n_mules=tr["mules"],
        gamma=tr["gamma"],
        freshness=FreshnessConfig(alpha=f["alpha"], beta=f["beta"],
                                  history=f["history"], warmup=f["warmup"],
                                  init_threshold=f["init_threshold"]))
    mob = {k: v for k, v in tr["mobility"].items() if k != "kind"}
    gen = commuter_stream(inputs.seeds["schedule"], tr["mules"], 1, **mob)
    mesh = dcfg = None
    if tr.get("mesh"):
        from repro.core.distributed import DistributedConfig
        from repro.launch.mesh import make_mule_mesh
        mesh = make_mule_mesh(tr["mesh"]["pod"], tr["mesh"]["data"])
        dcfg = DistributedConfig(pop=pcfg)
    return Program(pcfg, gen, sgd, batch_fn, tr["method"]["name"],
                   tr["chunk_len"], mesh, dcfg, with_context)
