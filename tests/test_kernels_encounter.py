"""encounter_mix kernel: interpret-mode vs oracle + semantic properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.gossip import (encounter_matrix, flatten_population,
                                    unflatten_population)
from repro.core.aggregation import masked_group_mean
from repro.kernels.encounter_mix.kernel import encounter_mix_pallas
from repro.kernels.encounter_mix.ref import encounter_mix_reference


def _setup(m, d, seed=0, n_areas=2, p_active=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pos = jax.random.uniform(ks[0], (m, 2))
    area = jax.random.randint(ks[1], (m,), 0, n_areas)
    w = jax.random.normal(ks[2], (m, d))
    active = (jax.random.uniform(ks[3], (m,)) < p_active)
    return pos, area, active, w


@pytest.mark.parametrize("m,d,block_m,block_d", [
    (20, 256, 8, 128),          # several row blocks, one d block
    (33, 130, 16, 128),         # ragged M and D (padding on both axes)
    (64, 1024, 64, 256),        # several d blocks
    (7, 5, 8, 128),             # smaller than one tile
    (1100, 200, 256, 128),      # three peer tiles, the last ragged
])
@pytest.mark.parametrize("p_active", [1.0, 0.6])
def test_pallas_matches_ref(m, d, block_m, block_d, p_active):
    pos, area, active, w = _setup(m, d, p_active=p_active)
    ref, ref_mass = encounter_mix_reference(pos, area, active, w,
                                            radius=0.3)
    out, mass = encounter_mix_pallas(pos, area, active, w, radius=0.3,
                                     block_m=block_m, block_d=block_d,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(mass), np.asarray(ref_mass))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ref_matches_dense_group_mean():
    """The fused op computes the same neighbor mean as the retired dense
    path (encounter matrix + per-leaf masked_group_mean), to float
    tolerance — it normalizes after the matmul instead of before."""
    pos, area, active, _ = _setup(24, 0, seed=3, p_active=0.7)
    models = {"a": jax.random.normal(jax.random.PRNGKey(5), (24, 3, 4)),
              "b": jax.random.normal(jax.random.PRNGKey(6), (24, 7))}
    enc = encounter_matrix(pos, area, 0.3, active).astype(jnp.float32)
    dense, dense_mass = masked_group_mean(models, enc)
    flat, spec = flatten_population(models)
    mixed, mass = encounter_mix_reference(pos, area, active, flat,
                                          radius=0.3)
    fused = unflatten_population(mixed, spec)
    np.testing.assert_array_equal(np.asarray(mass), np.asarray(dense_mass))
    for k in models:
        np.testing.assert_allclose(np.asarray(fused[k]),
                                   np.asarray(dense[k]), atol=1e-5)


def test_isolated_rows_are_zero_with_zero_mass():
    """No peer in radius/area (or inactive) -> zero mix row, zero mass."""
    pos = jnp.array([[0.0, 0.0], [0.05, 0.0], [0.9, 0.9], [0.0, 0.01]])
    area = jnp.array([0, 0, 0, 1])           # row 3: same spot, other area
    active = jnp.array([True, True, True, True])
    w = jnp.ones((4, 8))
    out, mass = encounter_mix_reference(pos, area, active, w, radius=0.15)
    np.testing.assert_array_equal(np.asarray(mass), [1, 1, 0, 0])
    assert np.all(np.asarray(out)[2:] == 0)
    # switching a peer off removes it from both sides
    out2, mass2 = encounter_mix_reference(
        pos, area, jnp.array([True, False, True, True]), w, radius=0.15)
    np.testing.assert_array_equal(np.asarray(mass2), [0, 0, 0, 0])
    assert np.all(np.asarray(out2) == 0)


def test_active_none_equals_all_ones():
    pos, area, active, w = _setup(16, 32, seed=9)
    a, am = encounter_mix_reference(pos, area, None, w, radius=0.3)
    b, bm = encounter_mix_reference(pos, area, jnp.ones((16,), bool), w,
                                    radius=0.3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(am), np.asarray(bm))
