"""Locality-aware ring exchange: the hop-prune predicate, bucket-sharding
helpers, the per-hop Pallas kernel, and pruned-vs-full ring parity.

The safety property the tier-1 half pins is one-sided: the area-bitmask
predicate may EXECUTE a hop it didn't need (hash collisions of
``area % N_AREA_BITS`` only add work), but it must never PRUNE a hop whose
two shard blocks share an active area — that would silently drop
encounters. The slow half replays every registered multi-area scenario
through the real sharded engine with pruning on and off and demands the
results agree (bitwise for oppcl, whose skipped hops leave its running
argmin untouched; to float tolerance for the mean-mix methods).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # tier-1 container
    from repro.testing.hypo import given, settings, strategies as st

from repro.baselines.gossip import (N_AREA_BITS, RingSpec, area_bits,
                                    hops_needed, ring_hop_mask)
from repro.core.distributed import (bucket_locality_fraction,
                                    bucket_mule_order, reorder_colocation,
                                    reorder_mule_state)


# ---------------------------------------------------------------------------
# hop-prune predicate
# ---------------------------------------------------------------------------


def test_area_bits_is_active_onehot_union():
    area = jnp.array([0, 1, 33, 5], jnp.int32)        # 33 collides with 1
    bits = np.asarray(area_bits(area))
    assert bits.shape == (N_AREA_BITS,)
    assert set(np.nonzero(bits)[0]) == {0, 1, 5}
    act = jnp.array([True, False, False, True])
    bits = np.asarray(area_bits(area, act))
    assert set(np.nonzero(bits)[0]) == {0, 5}          # inactive rows drop out
    assert not np.asarray(area_bits(area, jnp.zeros(4, bool))).any()


def test_hop_mask_prunes_disjoint_buckets():
    # bucket-ordered: one area per shard block -> only the local hop runs
    n, m = 8, 4
    area = np.repeat(np.arange(n, dtype=np.int32), m)
    mask = np.asarray(ring_hop_mask(area, None, n))
    assert mask.shape == (n,)
    assert mask[0] and not mask[1:].any()
    # shuffled mules defeat the predicate: every block holds every area
    rng = np.random.RandomState(0)
    mask = np.asarray(ring_hop_mask(rng.permutation(area), None, n))
    assert mask.all()


@settings(max_examples=60, deadline=None)
@given(n_shards=st.sampled_from([2, 4, 8]),
       m_loc=st.integers(1, 4),
       n_areas=st.integers(1, 40),
       seed=st.integers(0, 10 ** 6),
       p_active=st.floats(0.0, 1.0))
def test_hop_mask_never_prunes_a_shared_area_hop(n_shards, m_loc, n_areas,
                                                 seed, p_active):
    """Soundness: if ANY active row of shard i shares an area with any
    active row of shard (i - s) % n, hop s must be kept. (The converse is
    not required — ``area % 32`` collisions may keep extra hops.)"""
    rng = np.random.RandomState(seed)
    m = n_shards * m_loc
    area = rng.randint(0, n_areas, size=m).astype(np.int32)
    active = rng.rand(m) < p_active
    mask = np.asarray(ring_hop_mask(area, active, n_shards))
    blocks = [(set(area[k * m_loc:(k + 1) * m_loc]
                   [active[k * m_loc:(k + 1) * m_loc]]))
              for k in range(n_shards)]
    for s in range(n_shards):
        needed = any(blocks[i] & blocks[(i - s) % n_shards]
                     for i in range(n_shards))
        if needed:
            assert mask[s], (s, blocks)


def test_hops_needed_matches_pairwise_bit_intersection():
    all_bits = jnp.array([[1, 0, 0, 0], [0, 1, 0, 0],
                          [1, 0, 0, 0], [0, 0, 1, 0]], bool)
    # shift 2 pairs shard 2 with shard 0 (both bit 0); shift 1 and 3 pair
    # only disjoint rows
    assert np.asarray(hops_needed(all_bits)).tolist() == \
        [True, False, True, False]


def test_ring_spec_shift_perm_routes_shard_i_minus_s():
    ring = RingSpec(axis_name="data", axis_size=4)
    assert ring.shift_perm(1) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    # receiving side of shift s on shard i is (i - s) % n — the col0 rule
    for s in range(4):
        for src, dst in ring.shift_perm(s):
            assert src == (dst - s) % 4


# ---------------------------------------------------------------------------
# bucket sharding helpers
# ---------------------------------------------------------------------------


def test_bucket_order_groups_areas_and_reorders_consistently():
    rng = np.random.RandomState(1)
    m, t = 12, 5
    area = rng.randint(0, 3, size=m).astype(np.int32)
    order = bucket_mule_order(area)
    sorted_area = area[order]
    assert (np.diff(sorted_area) >= 0).all()           # grouped by bucket
    # stable: equal areas keep their original relative order
    for a in np.unique(area):
        assert (np.diff(order[sorted_area == a]) > 0).all()
    co = {"fixed_id": rng.randint(-1, 4, size=(t, m)).astype(np.int32),
          "exchange": rng.rand(t, m) < 0.5,
          "pos": rng.rand(t, m, 2).astype(np.float32),
          "area": area, "init_space": rng.randint(0, 4, size=m)}
    out = reorder_colocation(co, order)
    assert np.array_equal(out["area"], sorted_area)
    assert np.array_equal(out["fixed_id"], co["fixed_id"][:, order])
    assert np.array_equal(out["pos"], co["pos"][:, order])
    assert np.array_equal(out["init_space"], co["init_space"][order])
    state = {"mule_models": {"w": np.arange(m * 2.).reshape(m, 2)},
             "mule_ts": np.arange(m), "t": np.int32(3)}
    sout = reorder_mule_state(state, order)
    assert np.array_equal(sout["mule_models"]["w"],
                          state["mule_models"]["w"][order])
    assert np.array_equal(sout["mule_ts"], state["mule_ts"][order])
    assert sout["t"] == state["t"]                     # non-mule leaves kept


def test_bucket_locality_fraction_bounds():
    area = np.repeat(np.arange(4, dtype=np.int32), 4)
    assert bucket_locality_fraction(area, 4) == 1.0    # bucketed: all local
    inter = np.tile(np.arange(4, dtype=np.int32), 4)
    assert bucket_locality_fraction(inter, 4) == 0.0   # striped: none local
    assert bucket_locality_fraction(np.zeros(8, np.int32), 1) == 1.0
    frac = bucket_locality_fraction(inter[bucket_mule_order(inter)], 4)
    assert frac == 1.0                                 # ordering restores it


# ---------------------------------------------------------------------------
# per-hop kernel vs the block oracle
# ---------------------------------------------------------------------------


def _hop_case(seed, r, v, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.uniform(ks[0], (r, 2)),
            jax.random.randint(ks[1], (r,), 0, 3),
            jax.random.uniform(ks[2], (r,)) < 0.8,
            jax.random.uniform(ks[3], (v, 2)),
            jax.random.randint(ks[4], (v,), 0, 3),
            jax.random.uniform(ks[5], (v,)) < 0.8,
            jax.random.normal(ks[6], (v, d)))


@pytest.mark.parametrize("r,v,d,row0,col0", [
    (16, 16, 48, 0, 0),        # self block: diagonal excluded
    (16, 16, 48, 16, 48),      # disjoint offsets
    (12, 20, 7, 0, 8),         # overlapping id ranges, ragged shapes
    (8, 8, 8, 24, 24),
    (40, 1100, 20, 0, 40),     # three visiting tiles, the last ragged
])
def test_hop_kernel_matches_block_oracle(r, v, d, row0, col0):
    from repro.kernels.encounter_mix.kernel import encounter_hop_pallas
    from repro.kernels.encounter_mix.ref import encounter_block
    pos_r, area_r, act_r, pos_v, area_v, act_v, w = _hop_case(0, r, v, d)
    acc_ref, mass_ref = encounter_block(pos_r, area_r, act_r, row0,
                                        pos_v, area_v, act_v, col0,
                                        w, 0.3)
    acc, mass = encounter_hop_pallas(pos_r, area_r, act_r, row0,
                                     pos_v, area_v, act_v, col0, w,
                                     radius=0.3, block_m=8, block_d=128,
                                     interpret=True)
    assert mass_ref.sum() > 0                          # non-degenerate case
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_ref),
                               rtol=1e-6, atol=1e-6)


def test_encounter_block_hop_dispatch():
    from repro.kernels.encounter_mix.ops import encounter_block_hop
    from repro.kernels.encounter_mix.ref import encounter_block
    pos_r, area_r, act_r, pos_v, area_v, act_v, w = _hop_case(1, 16, 16, 32)
    ref = encounter_block(pos_r, area_r, act_r, 0, pos_v, area_v, act_v, 16,
                          w, 0.3)
    out = encounter_block_hop(pos_r, area_r, act_r, 0,
                              pos_v, area_v, act_v, 16, w, 0.3,
                              backend="ref")
    for a, b in zip(out, ref):                         # ref IS the oracle
        assert np.array_equal(np.asarray(a), np.asarray(b))
    out = encounter_block_hop(pos_r, area_r, act_r, 0,
                              pos_v, area_v, act_v, 16, w, 0.3,
                              backend="interpret")
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        encounter_block_hop(pos_r, area_r, act_r, 0,
                            pos_v, area_v, act_v, 16, w, 0.3,
                            backend="nope")


# ---------------------------------------------------------------------------
# engine plumbing on the single local device (fast tier)
# ---------------------------------------------------------------------------


def _tiny_mobile_setup(m=8, t=6):
    from conftest import linear_population_setup
    return linear_population_setup("mobile", n_mules=m, n_steps=t,
                                   init_threshold=1e9, warmup=10 ** 6)


def test_ring_prune_flag_is_identity_on_one_device():
    from repro.core.distributed import (DistributedConfig,
                                        to_distributed_state)
    from repro.launch.mesh import make_mule_mesh
    from repro.scenarios import run_population_distributed
    import dataclasses
    pop, co, batch_fn, train_fn, pcfg = _tiny_mobile_setup()
    mesh = make_mule_mesh(1, 1)
    key = jax.random.PRNGKey(2)
    outs = []
    for prune in (True, False):
        dcfg = dataclasses.replace(DistributedConfig(pop=pcfg),
                                   ring_prune=prune)
        f, _ = run_population_distributed(to_distributed_state(pop, dcfg),
                                          co, batch_fn, train_fn, dcfg,
                                          mesh, key, method="gossip")
        outs.append(f["mule_models"])
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mesh_none_uses_the_suggested_shape():
    from repro.core.distributed import (DistributedConfig,
                                        to_distributed_state)
    from repro.launch.mesh import make_mule_mesh
    from repro.scenarios import run_population_distributed
    pop, co, batch_fn, train_fn, pcfg = _tiny_mobile_setup()
    dcfg = DistributedConfig(pop=pcfg)
    dstate = to_distributed_state(pop, dcfg)
    key = jax.random.PRNGKey(4)
    auto, _ = run_population_distributed(dstate, co, batch_fn, train_fn,
                                         dcfg, None, key, method="gossip")
    explicit, _ = run_population_distributed(dstate, co, batch_fn, train_fn,
                                             dcfg, make_mule_mesh(1, 1), key,
                                             method="gossip")
    # one host device -> the auto path can only pick the (1, 1) mesh, so
    # the runs must be the same program: bitwise
    for a, b in zip(jax.tree.leaves(auto), jax.tree.leaves(explicit)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# pruned vs full ring on a real mesh, every registered multi-area scenario
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pruned_ring_parity_every_multi_area_scenario(multi_device_runner):
    """For each registered scenario whose colocation spans > 1 area, run
    gossip / oppcl / mlmule+gossip on a real (1, 4) data mesh with hop
    pruning on and off: oppcl must agree bitwise, the mean-mix methods to
    1e-5 (in practice a pruned hop contributes an exact +0.0, so these
    agree bitwise too). A bucket-ordered variant must actually prune."""
    multi_device_runner("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.baselines.gossip import ring_hop_mask
from repro.core.distributed import (DistributedConfig, bucket_mule_order,
                                    reorder_colocation, to_distributed_state)
from repro.core.freshness import FreshnessConfig
from repro.core.population import PopulationConfig, init_population
from repro.scenarios import (SCENARIOS, run_population_distributed)

F, M, T = 12, 8, 9
mesh = jax.make_mesh((1, 4), ("pod", "data"))
X = jax.random.normal(jax.random.PRNGKey(50), (M, 12, 5))
Y = jax.random.normal(jax.random.PRNGKey(60), (M, 12))

def train_fn(params, batch, key):
    xb, yb = batch
    g = jax.grad(lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(params)
    return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)

def batch_fn(key, t):
    idx = jax.random.randint(key, (M, 4), 0, X.shape[1])
    return {"fixed": None, "mule": (jnp.take_along_axis(X, idx[:, :, None], 1),
                                    jnp.take_along_axis(Y, idx, 1))}

pcfg = PopulationConfig(mode="mobile", n_fixed=F, n_mules=M,
                        freshness=FreshnessConfig(init_threshold=1e9,
                                                  warmup=10**6))
pop = init_population(jax.random.PRNGKey(0),
                      lambda k: {"w": jax.random.normal(k, (5,))}, pcfg)
dcfg = DistributedConfig(pop=pcfg)
dcfg_u = dataclasses.replace(dcfg, ring_prune=False)
dstate = to_distributed_state(pop, dcfg)
key = jax.random.PRNGKey(7)

multi = []
for name, spec in sorted(SCENARIOS.items()):
    co = spec.colocation(3, M, T)
    if len(np.unique(np.asarray(co["area"]))) < 2:
        continue
    multi.append(name)
    co = reorder_colocation(co, bucket_mule_order(co["area"]))
    for method in ("gossip", "oppcl", "mlmule+gossip"):
        fp, _ = run_population_distributed(dstate, co, batch_fn, train_fn,
                                           dcfg, mesh, key, method=method)
        fu, _ = run_population_distributed(dstate, co, batch_fn, train_fn,
                                           dcfg_u, mesh, key, method=method)
        for a, b in zip(jax.tree.leaves(fp["mule_models"]),
                        jax.tree.leaves(fu["mule_models"])):
            a, b = np.asarray(a), np.asarray(b)
            if method == "oppcl":
                assert np.array_equal(a, b), (name, method)
            else:
                err = float(np.max(np.abs(a - b)))
                assert err < 1e-5, (name, method, err)
assert multi, "no multi-area scenario registered?"

# the registered traces are area-0 heavy (one area spans >= 3 of the 4
# blocks, so every hop is genuinely needed); a BALANCED bucket-ordered
# multi-area population must actually prune on this mesh
area = np.asarray([0] * 4 + [1] * 4 + [2] * 4 + [3] * 4, np.int32)
mask = np.asarray(ring_hop_mask(area, None, 4))
assert mask[0] and (~mask).sum() == 3, mask.tolist()
print("OK", multi)
""", n_devices=4)
