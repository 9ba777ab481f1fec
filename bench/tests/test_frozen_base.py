"""A configuration with a frozen base held once, brought as files alone
(``stub/stub-lora.*``): a tiny next-token model whose mules train low-rank
adapters over a frozen embedding and dense layers that every mule shares.
The base is built once from the seed into the replay's context; the mules
and spaces hold, exchange and carry the adapters only.

On the CPU a whole run comes out correct, and comes out not correct with
half of each batch left out, with a program that reads a base drawn from
another key, and with a step that leaves the adapters unchanged. The chunk
program's arguments grow by the added mules' adapters and state, not by a
base per mule. A configuration without ``init_shared`` runs the harness's
code as before. Limits (``stub/limits-lora.json``) and the readings they
were set from are in PERF.md section 4."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import schedule
import work
from program import _resolve
from reference import Population
from spec import Cell
from test_run_faults import CELLS, _run, small_cell

STUB = os.path.join(os.path.dirname(os.path.abspath(__file__)), "stub")


def _json(name):
    with open(os.path.join(STUB, name)) as f:
        return json.load(f)


def _cell(mules=16):
    traffic = dict(_json("mlmule-commuter-m16.json"), mules=mules)
    return Cell(name="stub-lora-commuter", chips=1,
                config=_json("stub-lora.json"), traffic=traffic,
                limits=_json("limits-lora.json"), per_layer=[])


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.syspath_prepend(STUB)       # the program side's module
    return _cell()


def _nbytes(tree):
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def fault_sgd(cell, fault):
    """The program's context-taking SGD step with a fault planted: half of
    each batch left out, a base drawn from another key than the context's,
    or the adapters returned as they came."""
    fwd = _resolve(cell.config["program"]["forward"])
    loss = _resolve(cell.config["program"]["loss"])
    lr = cell.config["lr"]
    other = cell.reference.init_shared(jax.random.PRNGKey(1), cell.config)

    def sgd(params, batch, key, ctx):
        if fault == "adapters_unchanged":
            return params
        xb, yb = batch
        if fault == "half_batch":
            xb, yb = xb[:xb.shape[0] // 2], yb[:yb.shape[0] // 2]
        shared = other if fault == "other_base" else ctx["shared"]
        g = jax.grad(lambda p: loss(fwd(p, xb, shared), yb))(params)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    return sgd


FAULTS = ("half_batch", "other_base", "adapters_unchanged")


def test_sound_run_is_correct(cell, monkeypatch, tmp_path):
    out = _run(cell, monkeypatch, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(cell, fault, monkeypatch, tmp_path):
    out = _run(cell, monkeypatch, tmp_path, fault_sgd(cell, fault))
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_base_is_built_once_from_the_weights_seed(cell):
    ref = cell.reference
    inputs = program.make_inputs(cell, ref, 2 ** 31 + 12345)
    shared = inputs.context["shared"]
    assert _nbytes(shared) == 4 * cell.config["shared_params"]
    assert cell.config["shared_params"] >= 20 * cell.config["params_per_mule"]
    want = jax.jit(lambda k: ref.init_shared(k, cell.config))(
        program.shared_key(inputs.seeds))
    for a, b in zip(jax.tree.leaves(shared), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the base's key is none of the keys the weights already draw from
    k = jax.random.PRNGKey(inputs.seeds["weights"])
    base_key = np.asarray(program.shared_key(inputs.seeds))
    for other in jax.random.split(k):
        assert not np.array_equal(base_key, np.asarray(other))
    mule, fixed = program.weights(cell, ref, inputs)
    adapters = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0),
                                               cell.config))
    for models, n in ((mule, cell.traffic["mules"]),
                      (fixed, cell.traffic["spaces"])):
        assert jax.tree.structure(models) == jax.tree.structure(adapters)
        assert [l.shape for l in jax.tree.leaves(models)] == \
            [(n,) + l.shape for l in jax.tree.leaves(adapters)]


def _chunk(mules):
    """The stub's compiled chunk program at ``mules`` mules, and the bytes
    of each of its parameters by name (the argument path in its HLO)."""
    cell = _cell(mules)
    ref = cell.reference
    inputs = program.make_inputs(cell, ref, 977)
    prog = program.make_program(cell, inputs)
    state = prog.initial_state(*program.weights(cell, ref, inputs))
    last = jnp.zeros((mules,), jnp.int32)
    compiled = prog.compiled_chunk(state, last, inputs, inputs.key)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    size = {"f32": 4, "s32": 4, "u32": 4}
    params = {name: size[t] * int(np.prod([int(d) for d in dims.split(",")
                                           if d]))
              for t, dims, name in re.findall(
                  r"= (\w+)\[([0-9,]*)\]\S* parameter\(\d+\), "
                  r"metadata=\{op_name=\"([^\"]+)\"", entry)}
    return compiled, params


def test_base_is_held_once(monkeypatch):
    """The chunk program at 16 and 32 mules: one copy of the base among its
    arguments; 16 more mules add their adapters and a few hundred bytes
    each of their own state (timestamp, last space, pool, schedule draws),
    no base; and no copy of the base is baked into the program."""
    monkeypatch.syspath_prepend(STUB)
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = _cell().config
    base, adapters = 4 * cfg["shared_params"], 4 * cfg["params_per_mule"]
    args = {}
    for m in (16, 32):
        compiled, params = _chunk(m)
        args[m] = compiled.memory_analysis().argument_size_in_bytes
        assert sum(params.values()) == args[m]
        assert sum(v for k, v in params.items() if "'shared" in k) == base
        assert sum(v for k, v in params.items()
                   if "'mule_models" in k) == m * adapters
    grown = args[32] - args[16]
    assert 16 * adapters < grown <= 16 * (adapters + 256) < base
    big = min(int(np.prod(s)) for s in ((128, 32), (32, 128), (128, 128)))
    consts = re.findall(r"constant[^\n]*?tensor<([0-9x]+)x[a-z]",
                        compiled.as_text())
    assert all(np.prod([int(d) for d in c.split("x")]) < big
               for c in consts)


def test_binding_is_one_pair_per_program(cell):
    inputs = program.make_inputs(cell, cell.reference, 3)
    prog = program.make_program(cell, inputs)
    assert prog.with_context
    assert prog.engine_fns is prog.engine_fns
    replaced = dataclasses.replace(prog,
                                   train_fn=fault_sgd(cell, "half_batch"))
    assert replaced.engine_fns[0] is not prog.engine_fns[0]


@pytest.mark.parametrize("name", CELLS)
def test_without_base_runs_todays_code(name):
    """No ``init_shared``: no base in the context, the train step and batch
    draw go to the engine as they are, and the reference reads no base."""
    cell = small_cell(name)
    ref = cell.reference
    assert not hasattr(ref, "init_shared")
    inputs = program.make_inputs(cell, ref, 11)
    assert sorted(inputs.context) == ["pools", "x", "y"]
    prog = program.make_program(cell, inputs)
    assert not prog.with_context
    assert prog.engine_fns == (prog.train_fn, prog.batch_fn)
    draws = schedule.commuter_draws(inputs.seeds["schedule"],
                                    cell.traffic["mules"],
                                    cell.traffic["mobility"])
    assert Population(cell, ref, inputs.context, inputs.key,
                      draws).shared is None


def _chunk_text(prog, state, inputs, train_fn, batch_fn):
    from repro.scenarios.engine import get_compiled_chunk_replay
    gen = prog.generator.arrays()
    fn = get_compiled_chunk_replay(
        state, prog.generator, gen, batch_fn, inputs.context, inputs.key,
        train_fn, prog.pcfg, method=prog.method, eval_every=None,
        eval_fn=None, chunk_len=prog.chunk_len, donate=True)
    last = jnp.zeros((prog.pcfg.n_mules,), jnp.int32)
    return fn.lower(state, last, jnp.asarray(0, jnp.int32), gen, None,
                    inputs.context, inputs.key).as_text()


@pytest.mark.parametrize("name", CELLS)
def test_without_base_lowers_as_before(name):
    """The harness's SGD step lowers to the chunk program that a plain
    three-argument step, written as the harness had it, lowers to."""
    cell = small_cell(name)
    ref = cell.reference
    inputs = program.make_inputs(cell, ref, 13)
    prog = program.make_program(cell, inputs)
    state = prog.initial_state(*program.weights(cell, ref, inputs))
    fwd = _resolve(cell.config["program"]["forward"])
    loss = _resolve(cell.config["program"]["loss"])
    lr = cell.config["lr"]

    def sgd(params, b, key):
        xb, yb = b
        g = jax.grad(lambda p: loss(fwd(p, xb), yb))(params)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)

    assert _chunk_text(prog, state, inputs, *prog.engine_fns) == \
        _chunk_text(prog, state, inputs, sgd, prog.batch_fn)


def test_flops_follow_the_frozen_rule(cell):
    """XLA's count of one example's gradient with respect to the adapters
    lies at most 8% above the reference file's ``train_flops``; counting
    the base's weight gradients too would lie above XLA's."""
    cfg, ref = cell.config, cell.reference
    params = ref.init(jax.random.PRNGKey(0), cfg)
    shared = ref.init_shared(jax.random.PRNGKey(1), cfg)
    x, y = ref.make_data(jax.random.PRNGKey(2), cfg,
                         dict(cell.traffic["data"], per_class=1))
    grad = jax.grad(lambda p, s, x, y: ref.loss(ref.forward(p, x, None, s),
                                                y))
    cost = jax.jit(grad).lower(params, shared, x[:1], y[:1]).compile() \
        .cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = work.train_flops(cfg, ref)
    assert ours <= cost["flops"] <= 1.08 * ours
    d, h, v, t = cfg["embed"], cfg["hidden"], cfg["vocab"], cfg["seq"]
    assert ours + 2 * t * (d * h + h * v) > cost["flops"]
