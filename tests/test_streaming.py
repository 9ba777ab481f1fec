"""Streaming colocation engine: generators, parity, and the T-free cache.

The streamed replay's whole contract is "indistinguishable from the
materialized engine, minus the [T, M] memory" — so nearly every test here
is a bitwise pin: on-device ``generate_chunk`` against the host tensors
for every registered scenario (chunk boundaries included),
``run_population_streamed`` against ``run_population`` for every method,
evals included, single-host against distributed, and the procedural
commuter stream against an independent host re-derivation of its dwell
cadence. ``run_population`` is the same compiled chunk program over
``dense_colocation`` (the materialized tensors, sliced), so each streamed
pin compares a generator's ``expand`` with those tensors through one
program. The cache tests pin the perf claim: the compiled chunk program
must not depend on the horizon.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container: fixed-seed fallback sweep
    from repro.testing.hypo import given, settings, strategies as st

from repro.core.distributed import DistributedConfig, to_distributed_state
from repro.mobility import (CommuterStream, commuter_stream,
                            compact_colocation, dense_colocation,
                            dwell_exchange_flags, materialize_generator)
from repro.scenarios import (get_scenario, list_scenarios, run_population,
                             run_population_streamed, scenario_generator)
from repro.scenarios.engine import (_colocation_tensors, jit_cache_clear,
                                    jit_cache_stats)

from conftest import assert_trees_bitwise, linear_population_setup

M, T = 6, 30


def _expand_chunked(gen, n_steps, chunk_len):
    """Concatenate generate_chunk over an awkwardly-chunked horizon."""
    outs = []
    for t0 in range(0, n_steps, chunk_len):
        outs.append(gen.generate_chunk(None, t0,
                                       min(chunk_len, n_steps - t0)))
    area0 = np.asarray(outs[0]["area"])
    return {
        "fixed_id": np.concatenate(
            [np.asarray(o["fixed_id"]) for o in outs], 0),
        "exchange": np.concatenate(
            [np.asarray(o["exchange"]) for o in outs], 0),
        "pos": np.concatenate([np.asarray(o["pos"]) for o in outs], 0),
        "active": np.concatenate([np.asarray(o["active"]) for o in outs], 0),
        "area": (np.concatenate([np.asarray(o["area"]) for o in outs], 0)
                 if area0.ndim == 2 else area0),
    }


# ---------------------------------------------------------------------------
# generator <-> host-tensor parity over the whole registry
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 16),
       n_mules=st.integers(min_value=2, max_value=14),
       n_steps=st.integers(min_value=50, max_value=180),
       chunk_len=st.integers(min_value=7, max_value=48))
def test_every_scenario_streams_bitwise(seed, n_mules, n_steps, chunk_len):
    """On-device generate_chunk == host colocation tensors, bitwise, for
    every registered scenario — at chunk lengths that do NOT divide the
    horizon, so run boundaries straddle chunk boundaries."""
    for name in list_scenarios():
        spec = get_scenario(name)
        co = spec.colocation(seed, n_mules, n_steps)
        gen = scenario_generator(spec, seed, n_mules, n_steps,
                                 colocation=co)
        fid, exch, pos, area, act = _colocation_tensors(co)
        for g in (gen, dense_colocation(co)):
            got = _expand_chunked(g, n_steps, chunk_len)
            for key, ref in (("fixed_id", fid), ("exchange", exch),
                             ("pos", pos), ("active", act), ("area", area)):
                assert np.array_equal(got[key], np.asarray(ref)), \
                    f"{name}: {type(g).__name__} {key} != host tensors"


def test_scenario_generator_reuses_prebuilt_colocation():
    """Passing colocation= skips the rebuild but yields the same stream."""
    spec = get_scenario("commuter")
    co = spec.colocation(1, M, T)
    a = scenario_generator(spec, 1, M, T, colocation=co)
    b = scenario_generator("commuter", 1, M, T)
    assert_trees_bitwise(a.generate_chunk(None, 11, 9),
                         b.generate_chunk(None, 11, 9))


def test_compact_falls_back_to_exchange_rle_when_cadence_lies():
    """A schedule whose exchange is NOT dwell-cadence-shaped still streams
    bitwise — compaction detects the mismatch and RLE-encodes exchange."""
    co = get_scenario("commuter").colocation(0, M, 90)
    weird = dict(co)
    rng = np.random.RandomState(0)
    weird["exchange"] = (co["fixed_id"] >= 0) & (rng.rand(90, M) < 0.3)
    gen = compact_colocation(weird)
    assert gen._has_exchange_rle
    got = _expand_chunked(gen, 90, 28)
    assert np.array_equal(got["exchange"], weird["exchange"])
    assert np.array_equal(got["fixed_id"], np.asarray(co["fixed_id"]))


# ---------------------------------------------------------------------------
# the procedural commuter stream
# ---------------------------------------------------------------------------


def test_commuter_stream_exchange_matches_dwell_cadence():
    """Independent host check: materializing the procedural generator and
    re-deriving exchange from dwell runs reproduces its on-device flags —
    i.e. the closed-form run-start math (cross-midnight continuation
    included) agrees with the host dwell counter."""
    gen = commuter_stream(0, 16, 700)
    co = materialize_generator(gen, chunk_len=97)
    assert np.array_equal(
        dwell_exchange_flags(co["fixed_id"], gen.exchange_steps),
        co["exchange"])


def test_commuter_stream_compaction_roundtrip():
    """compact(materialize(gen)) expands exactly like gen itself, and uses
    the closed-form cadence (no RLE fallback) — the generator's exchange
    semantics are the engine's dwell semantics."""
    gen = commuter_stream(3, 10, 400)
    cg = compact_colocation(materialize_generator(gen), cadence=3)
    assert not cg._has_exchange_rle
    assert_trees_bitwise(gen.generate_chunk(None, 123, 50),
                         cg.generate_chunk(None, 123, 50))


def test_commuter_stream_is_registered_and_valid():
    spec = get_scenario("streaming_commuter")
    assert spec.generator is not None
    co = spec.colocation(0, 8, 120)
    fid = np.asarray(co["fixed_id"])
    assert fid.shape == (120, 8) and fid.min() >= -1 \
        and fid.max() < spec.n_fixed
    assert "init_space" in co and "init_area" in co


def test_commuter_stream_duty_cycle_churn_keeps_liveness():
    gen = commuter_stream(0, 9, 300, duty_period=40)
    co = materialize_generator(gen)
    act = np.asarray(co["active"])
    assert act.shape == (300, 9) and not act.all()
    assert act.any(axis=1).all(), "step with zero active mules"


def test_commuter_stream_memory_is_horizon_free():
    short = commuter_stream(0, 32, 100)
    long = commuter_stream(0, 32, 10 ** 7)
    assert short.schedule_bytes() == long.schedule_bytes()
    assert_trees_bitwise(short.arrays(), long.arrays())


# ---------------------------------------------------------------------------
# streamed replay == materialized replay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method",
                         ["mlmule", "gossip", "oppcl", "local",
                          "mlmule+gossip"])
def test_streamed_replay_matches_materialized(method):
    """run_population_streamed == run_population, bitwise, per method —
    with a chunk length that does not divide the horizon."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(7)
    gen = compact_colocation(co)
    ref, aux_ref = run_population(pop, co, batch_fn, train_fn, pcfg, key,
                                  method=method)
    st, aux = run_population_streamed(pop, gen, batch_fn, train_fn, pcfg,
                                      key, n_steps=T, chunk_len=8,
                                      method=method, donate=False)
    assert_trees_bitwise(ref, st, f"{method}: streamed state diverged")
    assert_trees_bitwise(aux_ref["last_fid"], aux["last_fid"])


def test_streamed_evals_match_materialized():
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(7)

    def eval_fn(state, last):
        return {"wmean": jax.tree.map(lambda l: l.mean(),
                                      state["mule_models"]),
                "lmax": last.max()}

    ref, aux_ref = run_population(pop, co, batch_fn, train_fn, pcfg, key,
                                  eval_every=5, eval_fn=eval_fn)
    st, aux = run_population_streamed(pop, compact_colocation(co), batch_fn,
                                      train_fn, pcfg, key, n_steps=T,
                                      chunk_len=10, eval_every=5,
                                      eval_fn=eval_fn, donate=False)
    assert_trees_bitwise(ref, st)
    assert_trees_bitwise(aux_ref["evals"], aux["evals"])
    np.testing.assert_array_equal(aux_ref["eval_steps"], aux["eval_steps"])


def test_materialized_replay_is_one_dense_chunk():
    """run_population is the chunk replay over dense_colocation in one
    chunk of length T: the streamed call on the same arguments hits the
    program run_population compiled, with no retrace, and agrees bitwise."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(13)
    jit_cache_clear()
    ref, aux_ref = run_population(pop, co, batch_fn, train_fn, pcfg, key,
                                  method="gossip")
    assert jit_cache_stats() == {"traces": 1, "hits": 0, "misses": 1}
    st, aux = run_population_streamed(pop, dense_colocation(co), batch_fn,
                                      train_fn, pcfg, key, chunk_len=T,
                                      method="gossip", donate=False)
    assert jit_cache_stats() == {"traces": 1, "hits": 1, "misses": 1}
    assert_trees_bitwise(ref, st, "dense chunk diverged from run_population")
    assert_trees_bitwise(aux_ref["last_fid"], aux["last_fid"])


def test_one_chunk_takes_a_partial_eval_tail():
    """One chunk whose length is not a multiple of eval_every evals like
    run_population (a partial tail block after the last eval) and like a
    multi-chunk run whose chunks are whole eval blocks."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(17)

    def eval_fn(state, last):
        return {"wmean": jax.tree.map(lambda l: l.mean(),
                                      state["mule_models"]),
                "lmax": last.max()}

    ref, aux_ref = run_population(pop, co, batch_fn, train_fn, pcfg, key,
                                  eval_every=7, eval_fn=eval_fn)
    np.testing.assert_array_equal(aux_ref["eval_steps"], [6, 13, 20, 27])
    for gen, chunk_len in ((dense_colocation(co), T),
                           (compact_colocation(co), 14)):
        st, aux = run_population_streamed(pop, gen, batch_fn, train_fn,
                                          pcfg, key, n_steps=T,
                                          chunk_len=chunk_len, eval_every=7,
                                          eval_fn=eval_fn, donate=False)
        assert_trees_bitwise(ref, st)
        assert_trees_bitwise(aux_ref["evals"], aux["evals"])
        np.testing.assert_array_equal(aux_ref["eval_steps"],
                                      aux["eval_steps"])


@pytest.mark.parametrize("make_gen", [dense_colocation, compact_colocation])
def test_multi_chunk_run_rejects_misaligned_evals(make_gen):
    """Two chunks (T - 1 steps, then one) with eval_every not dividing the
    chunk length still raise, whatever the generator."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    with pytest.raises(ValueError, match="multiple of"):
        run_population_streamed(pop, make_gen(co), batch_fn, train_fn, pcfg,
                                jax.random.PRNGKey(0), n_steps=T,
                                chunk_len=T - 1, eval_every=5,
                                eval_fn=lambda s, l: l.max(), donate=False)


def test_streamed_rejects_misaligned_eval_chunks():
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    with pytest.raises(ValueError, match="multiple of"):
        run_population_streamed(pop, compact_colocation(co), batch_fn,
                                train_fn, pcfg, jax.random.PRNGKey(0),
                                n_steps=T, chunk_len=8, eval_every=5,
                                eval_fn=lambda s, l: l.max(), donate=False)


def test_streamed_stacked_batches_match():
    """Stacked [T, ...] batch pytrees slice per chunk like the scan does."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, T)
    stacked = jax.vmap(lambda k: batch_fn(k, 0))(ks)
    ref, _ = run_population(pop, co, stacked, train_fn, pcfg, key)
    st, _ = run_population_streamed(pop, compact_colocation(co), stacked,
                                    train_fn, pcfg, key, n_steps=T,
                                    chunk_len=8, donate=False)
    assert_trees_bitwise(ref, st, "stacked-batch streamed run diverged")


def test_streamed_registered_scenario_end_to_end():
    """streaming_commuter: native generator vs its materialized builder."""
    spec = get_scenario("streaming_commuter")
    pop, _, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    co = spec.colocation(0, M, T)
    gen = scenario_generator(spec, 0, M, T)
    assert isinstance(gen, CommuterStream)
    key = jax.random.PRNGKey(11)
    ref, _ = run_population(pop, co, batch_fn, train_fn, pcfg, key)
    st, _ = run_population_streamed(pop, gen, batch_fn, train_fn, pcfg, key,
                                    chunk_len=7, donate=False)
    assert_trees_bitwise(ref, st, "streaming_commuter diverged")


# ---------------------------------------------------------------------------
# the horizon-free jit cache + donation
# ---------------------------------------------------------------------------


def test_chunk_cache_is_horizon_free():
    """Replays of different lengths (and fresh same-shape generators) hit
    one compiled chunk program: zero new traces."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(1)
    jit_cache_clear()
    run_population_streamed(pop, compact_colocation(co), batch_fn, train_fn,
                            pcfg, key, n_steps=24, chunk_len=8,
                            donate=False)
    t1 = jit_cache_stats()["traces"]
    assert t1 == 1, "full-size chunks should share one trace"
    run_population_streamed(pop, compact_colocation(co), batch_fn, train_fn,
                            pcfg, key, n_steps=16, chunk_len=8,
                            donate=False)
    assert jit_cache_stats()["traces"] == t1, \
        "a new horizon retraced the chunk program"


def test_streamed_donation_runs_in_place():
    """donate=True (the default) frees the carry each chunk; results match
    an undonated run."""
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    key = jax.random.PRNGKey(2)
    gen = compact_colocation(co)
    ref, _ = run_population_streamed(pop, gen, batch_fn, train_fn, pcfg,
                                     key, n_steps=T, chunk_len=8,
                                     donate=False)
    donor = jax.tree.map(jnp.copy, pop)
    st, _ = run_population_streamed(donor, gen, batch_fn, train_fn, pcfg,
                                    key, n_steps=T, chunk_len=8)
    assert_trees_bitwise(ref, st, "donated streamed run diverged")


# ---------------------------------------------------------------------------
# distributed streaming (1-device mesh: shard_map is exact in tier-1)
# ---------------------------------------------------------------------------


def _mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("pod", "data"))


@pytest.mark.parametrize("method", ["mlmule", "gossip", "oppcl"])
def test_distributed_streamed_matches_single_host(method):
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    dcfg = DistributedConfig(pop=pcfg)
    dstate = to_distributed_state(pop, dcfg)
    key = jax.random.PRNGKey(3)
    gen = compact_colocation(co)
    ref, aux_ref = run_population_streamed(pop, gen, batch_fn, train_fn,
                                           pcfg, key, n_steps=T,
                                           chunk_len=8, method=method,
                                           donate=False)
    st, aux = run_population_streamed(dstate, gen, batch_fn, train_fn,
                                      pcfg, key, n_steps=T, chunk_len=8,
                                      method=method, donate=False,
                                      mesh=_mesh(), dcfg=dcfg)
    assert_trees_bitwise({k: ref[k] for k in ("mule_models", "mule_ts")
                          if k in ref},
                         {k: st[k] for k in ("mule_models", "mule_ts")
                          if k in st},
                         f"{method}: distributed streamed diverged")
    assert_trees_bitwise(aux_ref["last_fid"], aux["last_fid"])


def test_distributed_streamed_requires_dcfg():
    pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    with pytest.raises(ValueError, match="mesh requires dcfg"):
        run_population_streamed(pop, compact_colocation(co), batch_fn,
                                train_fn, pcfg, jax.random.PRNGKey(0),
                                n_steps=T, mesh=_mesh())


@pytest.mark.slow
def test_distributed_streamed_multi_device_shards_generator():
    """On a real multi-device mesh each shard expands only its own mule
    columns; the result still matches single-host bitwise (mlmule's psum
    schedule is shard-count invariant)."""
    from conftest import run_with_devices
    code = """
import jax, numpy as np
import jax.numpy as jnp
from repro.core.distributed import DistributedConfig, to_distributed_state
from repro.mobility import compact_colocation
from repro.scenarios import run_population_streamed
import sys, os
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
from conftest import linear_population_setup, assert_trees_bitwise

M, T = 8, 30
pop, co, batch_fn, train_fn, pcfg = linear_population_setup(
    n_mules=M, n_steps=T)
dcfg = DistributedConfig(pop=pcfg)
dstate = to_distributed_state(pop, dcfg)
key = jax.random.PRNGKey(5)
gen = compact_colocation(co)
mesh = jax.sharding.Mesh(
    np.array(jax.devices()[:4]).reshape(1, 4), ("pod", "data"))
ref, _ = run_population_streamed(pop, gen, batch_fn, train_fn, pcfg, key,
                                 n_steps=T, chunk_len=8, donate=False)
st, _ = run_population_streamed(dstate, gen, batch_fn, train_fn, pcfg, key,
                                n_steps=T, chunk_len=8, donate=False,
                                mesh=mesh, dcfg=dcfg)
assert_trees_bitwise(ref["mule_models"], st["mule_models"])
print("MULTIDEV_STREAM_OK")
"""
    assert "MULTIDEV_STREAM_OK" in run_with_devices(code, n_devices=4)


# ---------------------------------------------------------------------------
# _colocation_tensors: device arrays pass through without a host round-trip
# ---------------------------------------------------------------------------


def test_colocation_tensors_keep_device_arrays():
    """A device-resident colocation dict is not copied through the host:
    right-dtype arrays come back as the same object."""
    co = get_scenario("commuter").colocation(0, M, T)
    dev = {
        "fixed_id": jnp.asarray(co["fixed_id"], jnp.int32),
        "exchange": jnp.asarray(co["exchange"], bool),
        "pos": jnp.asarray(co["pos"], jnp.float32),
        "area": jnp.asarray(co["area"], jnp.int32),
    }
    fid, exch, pos, area, act = _colocation_tensors(dev)
    assert fid is dev["fixed_id"]
    assert exch is dev["exchange"]
    assert pos is dev["pos"]
    assert area is dev["area"]
    # host inputs still upload + normalize like before
    fid2, *_ = _colocation_tensors(co)
    assert np.array_equal(np.asarray(fid), np.asarray(fid2))


def test_colocation_tensors_cast_wrong_dtype_on_device():
    co = get_scenario("commuter").colocation(0, M, T)
    dev = {"fixed_id": jnp.asarray(co["fixed_id"], jnp.int64)
           if jax.config.jax_enable_x64 else
           jnp.asarray(co["fixed_id"], jnp.int16),
           "exchange": jnp.asarray(co["exchange"])}
    fid, exch, *_ = _colocation_tensors(dev)
    assert fid.dtype == jnp.int32
    assert np.array_equal(np.asarray(fid), co["fixed_id"])
