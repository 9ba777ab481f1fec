"""mule_agg kernel: interpret-mode vs oracle + hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container: fixed-seed fallback sweep
    from repro.testing.hypo import given, settings, strategies as st

from repro.core.aggregation import masked_group_mean, weighted_average
from repro.kernels.mule_agg.kernel import mule_agg_pallas
from repro.kernels.mule_agg.ref import mule_agg_reference


@pytest.mark.parametrize("f,m,d,block_d", [
    (8, 20, 256, 128), (8, 20, 1000, 256), (2, 3, 64, 64),
    (16, 64, 4096, 2048), (1, 1, 130, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_ref(f, m, d, block_d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    assign = jax.random.uniform(k1, (f, m), jnp.float32)
    w = jax.random.normal(k2, (m, d), dtype)
    ref = mule_agg_reference(assign, w)
    out = mule_agg_pallas(assign, w, block_d=block_d, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_accumulates_over_mule_tiles(dtype):
    # M=1100 is three mule tiles of the reduction, the last one ragged: the
    # f32 accumulator must carry across them and the interpreter's NaN
    # padding past M must be masked out. Rows are normalized as the engine's
    # dwell weights are, so values stay O(1) and the f32 tolerance holds.
    f, m, d = 4, 1100, 300
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    assign = jax.random.uniform(k1, (f, m), jnp.float32)
    assign = assign / assign.sum(1, keepdims=True)
    w = jax.random.normal(k2, (m, d), dtype)
    ref = mule_agg_reference(assign, w)
    out = mule_agg_pallas(assign, w, block_d=128, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@settings(max_examples=25, deadline=None)
@given(f=st.integers(1, 6), m=st.integers(1, 12), d=st.integers(1, 64),
       seed=st.integers(0, 10_000))
def test_group_mean_convexity(f, m, d, seed):
    """Group means lie inside the convex hull of member values (per coord)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (m, d))
    assign = (jax.random.uniform(k2, (f, m)) > 0.5).astype(jnp.float32)
    models = {"w": w}
    out, mass = masked_group_mean(models, assign)
    for fi in range(f):
        members = np.where(np.asarray(assign)[fi] > 0)[0]
        if len(members) == 0:
            continue
        sub = np.asarray(w)[members]
        got = np.asarray(out["w"])[fi]
        assert (got <= sub.max(0) + 1e-5).all()
        assert (got >= sub.min(0) - 1e-5).all()


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 8), d=st.integers(1, 32), seed=st.integers(0, 10_000))
def test_weighted_average_affine_equivariance(m, d, seed):
    """avg(a*W + b) == a*avg(W) + b — aggregation must be affine."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (m, d))
    weights = jax.random.uniform(k2, (m,)) + 0.1
    base = weighted_average({"w": w}, weights)["w"]
    shifted = weighted_average({"w": 2.5 * w - 1.0}, weights)["w"]
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(2.5 * base - 1.0),
                               atol=1e-5)


def test_group_mean_pallas_backend():
    models = {"a": jax.random.normal(jax.random.PRNGKey(0), (10, 33)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (10, 4, 7))}
    assign = (jax.random.uniform(jax.random.PRNGKey(2), (4, 10)) > 0.4).astype(jnp.float32)
    ref, mass_r = masked_group_mean(models, assign, backend="ref")
    out, mass_p = masked_group_mean(models, assign, backend="interpret")
    for k in models:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mass_r), np.asarray(mass_p))
