"""``program.bind_context`` hands the replay's context to a train function
that reads it, ``(params, batch, key, context)``, through the engine's
``(params, batch, key)`` train step: the engine draws the batch with the
context inside the compiled step, and the train step closes over it.

A tiny linear population whose every mule reads one frozen base from the
context replays bitwise alike through every engine path the harness can
drive, for ML Mule, gossip, OppCL and local training: the streamed and the
materialized single-host scans against the per-step loop with the same
binding; on four host devices (``conftest.py``), the distributed scan and
its streamed form against the distributed per-step loop. Bound, it replays
bitwise as a three-argument train step that closes over the same base.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from program import bind_context

M, F, T = 8, 4, 18
METHODS = ["mlmule", "gossip", "oppcl", "local"]


def _setup():
    from repro.core.freshness import FreshnessConfig
    from repro.core.population import PopulationConfig, init_population
    from repro.scenarios import walk_colocation
    ctx = {"x": jax.random.normal(jax.random.PRNGKey(50), (M, 12, 5)),
           "y": jax.random.normal(jax.random.PRNGKey(60), (M, 12)),
           "shared": {"w0": jax.random.normal(jax.random.PRNGKey(70),
                                              (5, 5))}}

    def loss(p, batch, w0):
        xb, yb = batch
        return jnp.mean(((xb @ w0) @ p["w"] - yb) ** 2)

    def train(params, batch, key, c):
        g = jax.grad(loss)(params, batch, c["shared"]["w0"])
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

    def batch_fn(key, t, c):
        idx = jax.random.randint(key, (M, 4), 0, 12)
        return {"fixed": None,
                "mule": (jnp.take_along_axis(c["x"], idx[:, :, None], 1),
                         jnp.take_along_axis(c["y"], idx, 1))}

    pcfg = PopulationConfig(mode="mobile", n_fixed=F, n_mules=M,
                            freshness=FreshnessConfig())
    pop = init_population(jax.random.PRNGKey(0),
                          lambda k: {"w": jax.random.normal(k, (5,))}, pcfg)
    return pop, walk_colocation(0, M, T), ctx, train, batch_fn, pcfg


def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _mesh(n):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]).reshape(1, n),
                             ("pod", "data"))


@pytest.mark.parametrize("method", METHODS)
def test_single_host_scans_match_loop(method):
    from repro.mobility import compact_colocation
    from repro.scenarios import (run_population, run_population_streamed,
                                 run_population_loop)
    pop, co, ctx, train, batch_fn, pcfg = _setup()
    key = jax.random.PRNGKey(7)
    tr, bt = bind_context(train, batch_fn)
    ref, _ = run_population_loop(pop, co, bt, tr, pcfg, key, method=method,
                                 context=ctx)
    tr, bt = bind_context(train, batch_fn)
    scan, _ = run_population(pop, co, bt, tr, pcfg, key, method=method,
                             context=ctx)
    tr, bt = bind_context(train, batch_fn)
    streamed, _ = run_population_streamed(
        pop, compact_colocation(co), bt, tr, pcfg, key, n_steps=T,
        chunk_len=8, method=method, context=ctx, donate=False)
    _same(ref, scan)
    _same(ref, streamed)
    assert not np.array_equal(np.asarray(ref["mule_models"]["w"]),
                              np.asarray(pop["mule_models"]["w"]))


@pytest.mark.parametrize("method", METHODS)
def test_distributed_scans_match_loop(method):
    from repro.core.distributed import DistributedConfig, to_distributed_state
    from repro.mobility import compact_colocation
    from repro.scenarios import run_population_streamed
    from repro.scenarios.engine import (run_population_distributed,
                                        run_population_distributed_loop)
    assert len(jax.devices()) >= 4
    pop, co, ctx, train, batch_fn, pcfg = _setup()
    dcfg = DistributedConfig(pop=pcfg)
    dstate = to_distributed_state(pop, dcfg)
    mesh, key = _mesh(4), jax.random.PRNGKey(3)
    tr, bt = bind_context(train, batch_fn)
    ref, _ = run_population_distributed_loop(dstate, co, bt, tr, dcfg, mesh,
                                             key, method=method, context=ctx)
    tr, bt = bind_context(train, batch_fn)
    scan, _ = run_population_distributed(dstate, co, bt, tr, dcfg, mesh, key,
                                         method=method, context=ctx)
    tr, bt = bind_context(train, batch_fn)
    streamed, _ = run_population_streamed(
        dstate, compact_colocation(co), bt, tr, pcfg, key, n_steps=T,
        chunk_len=8, method=method, context=ctx, donate=False, mesh=mesh,
        dcfg=dcfg)
    _same(ref, scan)
    _same(ref, streamed)


def test_bound_matches_closure_over_the_base():
    from repro.mobility import compact_colocation
    from repro.scenarios import run_population_streamed
    pop, co, ctx, train, batch_fn, pcfg = _setup()
    key = jax.random.PRNGKey(9)
    tr, bt = bind_context(train, batch_fn)
    bound, _ = run_population_streamed(
        pop, compact_colocation(co), bt, tr, pcfg, key, n_steps=T,
        chunk_len=8, context=ctx, donate=False)
    closed, _ = run_population_streamed(
        pop, compact_colocation(co), batch_fn,
        lambda p, b, k: train(p, b, k, ctx), pcfg, key, n_steps=T,
        chunk_len=8, context=ctx, donate=False)
    _same(bound, closed)


def test_train_step_without_a_draw_raises():
    _, _, ctx, train, batch_fn, _ = _setup()
    tr, _ = bind_context(train, batch_fn)
    with pytest.raises(IndexError):
        tr({"w": jnp.zeros((5,))}, (ctx["x"][0], ctx["y"][0]), None)
