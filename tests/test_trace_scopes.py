"""Named layers of the compiled replay.

The step's ``jax.named_scope`` names (``mule_expand``, ``mule_train``,
``mule_fresh``, ``mule_space``, ``mule_peer``) must reach the streamed
chunk program's HLO ``op_name`` metadata, since a TPU trace names an
operation only by its instruction and the benchmark's trace reduction
charges device time by the last ``mule_*`` scope of that metadata. Pinned
here on tiny widths, single-host and on a mesh over the suite's host
devices: each scope appears, every contraction, loop and conditional of
the step lies under one, the freshness push's ring write (a select over
mules, spaces and ring slots, with no loop) under ``mule_fresh``, and the
scopes change nothing but metadata. The streamed replay's host
span ``mule/chunk`` is read back from a profiler trace, one per chunk.
"""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import DistributedConfig, to_distributed_state
from repro.core.freshness import FreshnessConfig
from repro.core.population import PopulationConfig, init_population
from repro.mobility import commuter_stream
from repro.scenarios import run_population_streamed
from repro.scenarios.engine import get_compiled_chunk_replay, jit_cache_clear

SCOPES = ("mule_expand", "mule_train", "mule_fresh", "mule_space",
          "mule_peer")
EXPECTED = {"mlmule": {"mule_expand", "mule_train", "mule_fresh",
                       "mule_space"},
            "gossip": {"mule_expand", "mule_train", "mule_peer"}}
HEAVY = ("dot", "convolution", "while", "conditional")
F, CHUNK, D_IN, D_OUT = 8, 4, 6, 3
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"\s(" + "|".join(HEAVY) + r")\(")
_SCOPE = re.compile(r"(?<!\w)(" + "|".join(SCOPES) + r")(?!\w)")


def _setup(method, n_mules, mesh=None):
    """Tiny softmax-regression mules on the procedural commuter stream."""
    k = jax.random.PRNGKey(0)
    context = {"x": jax.random.normal(k, (32, D_IN)),
               "y": jnp.arange(32, dtype=jnp.int32) % D_OUT,
               "pools": jnp.arange(n_mules * 4, dtype=jnp.int32)
               .reshape(n_mules, 4) % 32}

    def train_fn(params, batch, key):
        xb, yb = batch

        def loss(p):
            logits = jnp.dot(xb, p["w"])
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), yb[:, None], axis=1))
        g = jax.grad(loss)(params)
        return jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g)

    def batch_fn(key, t, ctx):
        j = jax.random.randint(key, (n_mules, 2), 0, ctx["pools"].shape[1])
        idx = jnp.take_along_axis(ctx["pools"], j, axis=1)
        return {"fixed": None, "mule": (ctx["x"][idx], ctx["y"][idx])}

    pcfg = PopulationConfig(mode="mobile", n_fixed=F, n_mules=n_mules,
                            freshness=FreshnessConfig(warmup=2))
    state = init_population(
        jax.random.PRNGKey(1),
        lambda kk: {"w": jax.random.normal(kk, (D_IN, D_OUT))}, pcfg)
    gen = commuter_stream(0, n_mules, 64)
    dcfg = None
    if mesh is not None:
        dcfg = DistributedConfig(pop=pcfg)
        state = to_distributed_state(state, dcfg)
    return state, gen, batch_fn, train_fn, pcfg, context, dcfg


def _mesh():
    devs = jax.devices()
    return jax.sharding.Mesh(np.array(devs).reshape(1, len(devs)),
                             ("pod", "data"))


def _chunk_hlo(method, distributed):
    mesh = _mesh() if distributed else None
    n_mules = 8 * (len(jax.devices()) if distributed else 2)
    state, gen, batch_fn, train_fn, pcfg, ctx, dcfg = _setup(
        method, n_mules, mesh)
    key = jax.random.PRNGKey(2)
    gen_arrays = gen.arrays()
    jit_cache_clear()
    fn = get_compiled_chunk_replay(
        state, gen, gen_arrays, batch_fn, ctx, key, train_fn, pcfg,
        method=method, eval_every=None, eval_fn=None, chunk_len=CHUNK,
        donate=False, mesh=mesh, dcfg=dcfg)
    last = jnp.zeros((n_mules,), jnp.int32)
    text = fn.lower(state, last, jnp.asarray(0, jnp.int32), gen_arrays,
                    None, ctx, key).compile().as_text()
    jit_cache_clear()
    return text


def _instructions(text):
    """(name, opcode or None, op_name) of every instruction with metadata."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(m.group(2))
        if op is None:
            continue
        rhs = m.group(2).split(", metadata=", 1)[0]
        code = _OPCODE.search(rhs)
        out.append((m.group(1), code.group(1) if code else None,
                    op.group(1)))
    return out


def _in_step(ins):
    """Contractions, loops and conditionals inside the chunk scan's body,
    but for the key derivation's threefry: the CPU lowers it to a rolled
    loop (the TPU unrolls it), and the step's key folds are unscoped."""
    return [(n, c, o) for n, c, o in ins
            if c is not None and "/while/body/" in o
            and "jit(_threefry" not in o]


def _layer(op_name):
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


CASES = [("mlmule", False), ("gossip", False), ("mlmule", True),
         ("gossip", True)]


@pytest.fixture(scope="module")
def hlo():
    return {case: _chunk_hlo(*case) for case in CASES}


@pytest.mark.parametrize("method,distributed", CASES)
def test_every_layer_scope_reaches_the_chunk_program(hlo, method,
                                                     distributed):
    ins = _instructions(hlo[(method, distributed)])
    present = {_layer(o) for _, _, o in ins} - {None}
    assert present == EXPECTED[method]


@pytest.mark.parametrize("method,distributed", CASES)
def test_heavy_instructions_of_the_step_are_scoped(hlo, method,
                                                   distributed):
    """Every contraction, loop and conditional inside the scan's body (the
    step) carries a layer scope; only the chunk's own scan loop may not."""
    in_step = _in_step(_instructions(hlo[(method, distributed)]))
    assert in_step
    bare = [(n, o) for n, c, o in in_step if _layer(o) is None]
    assert not bare, bare
    assert {c for _, c, _ in in_step} >= {"dot"}
    if method == "gossip":
        conds = [o for _, c, o in in_step if c == "conditional"]
        assert conds and all("mule_peer" in o for o in conds)


def test_freshness_push_loop_is_under_mule_fresh(hlo):
    """The single-host push runs no loop: no ``while`` lies under
    ``mule_fresh``, and its ring write, a select over the [M, F, K] slot
    one-hot, does."""
    ins = _instructions(hlo[("mlmule", False)])
    assert not [o for _, c, o in ins
                if c == "while" and _layer(o) == "mule_fresh"]
    n_mules, ring = 16, FreshnessConfig().history
    writes = [o for shape, o in _selects(hlo[("mlmule", False)])
              if _layer(o) == "mule_fresh"
              and np.prod(shape) == n_mules * F * ring]
    assert writes


def _selects(text):
    """(shape, op_name) of every ``select`` instruction with metadata."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        sel = m and re.match(r"\w+\[([\d,]*)\]\S* select\(", m.group(2))
        op = sel and _OP_NAME.search(m.group(2))
        if op:
            out.append(([int(d) for d in sel.group(1).split(",") if d],
                        op.group(1)))
    return out


def _strip(text):
    """HLO text without metadata and the debug tables it points into."""
    body = text.split("\nFileNames", 1)[0]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


@pytest.mark.parametrize("method,distributed", CASES)
def test_scopes_change_only_metadata(hlo, monkeypatch, method, distributed):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _chunk_hlo(method, distributed)
    assert all(_layer(o) is None for _, _, o in _instructions(bare))
    assert _strip(bare) == _strip(hlo[(method, distributed)])


def test_streamed_replay_emits_one_chunk_span_per_chunk(tmp_path):
    from jax.profiler import ProfileData
    state, gen, batch_fn, train_fn, pcfg, ctx, _ = _setup("mlmule", 16)
    n_chunks = 3
    with jax.profiler.trace(str(tmp_path)):
        final, _ = run_population_streamed(
            state, gen, batch_fn, train_fn, pcfg, jax.random.PRNGKey(2),
            n_steps=n_chunks * CHUNK, chunk_len=CHUNK, context=ctx,
            donate=False)
        jax.block_until_ready(final)
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "mule/chunk"]
    assert sorted(s["t0"] for s in spans) == [0, CHUNK, 2 * CHUNK]
    assert all(s["steps"] == CHUNK for s in spans)
