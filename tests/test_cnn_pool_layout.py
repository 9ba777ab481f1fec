"""The image mule's first conv block in its pool-window-major layout.

``repro.models.cnn.cnn_forward`` runs block 1 on [B, H/2, W/2, 4*C]
activations, the four pixels of each 2x2 pool window as four channel groups:
the image by space to depth, conv1 as a 3x3 convolution with an expanded
[3, 3, 4*3, 4*C] kernel, batch norm over the groups, and the pool as a max
over them whose gradient goes to each window's first maximum. Pinned here
against the plain NHWC block (``conv_general_dilated``, ``reduce_window``)
kept in this file: the forward and every gradient leaf, for one model and a
vmapped population; tie routing bitwise against ``select-and-scatter``; the
kernel expansion exact; odd image sides refused.

The two formulations are compared in float64 at ``highest``. In float32
their sums run in other orders, and a ReLU or a pool window whose decision
lies within rounding of its boundary then sends one window's gradient
elsewhere: one such window moved the ``bn1`` bias gradient of one image by
3e-3 relative, with the forward 1e-6 apart.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mule_cnn import CNNConfig
from repro.models.cnn import (_bn, _conv2d, _expand_conv1, _pool,
                              _pool_groups, _space_to_depth, cnn_forward,
                              init_cnn, xent_loss)

N_MULES = 3


def plain_forward(params, images):
    """The paper CNN with both blocks in NHWC."""
    x = _pool(jax.nn.relu(_bn(_conv2d(images, params["conv1"]),
                              params["bn1"])))
    x = _pool(jax.nn.relu(_bn(_conv2d(x, params["conv2"]), params["bn2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["fc1_b"])
    return x @ params["fc2"] + params["fc2_b"]


def _params(key, cfg):
    """``init_cnn`` with batch-norm scales and biases moved off 1 and 0."""
    kp, ks, kb = jax.random.split(key, 3)
    p = init_cnn(kp, cfg)
    for i, bn in enumerate(("bn1", "bn2")):
        f = p[bn]["scale"].shape
        p[bn] = {"scale": 1 + 0.2 * jax.random.normal(
                     jax.random.fold_in(ks, i), f),
                 "bias": 0.2 * jax.random.normal(jax.random.fold_in(kb, i), f)}
    return p


def _inputs(seed, batch, size, n=None):
    """One model (``n`` None) or ``n`` stacked models, with their images
    and labels."""
    cfg = CNNConfig(image_size=size)
    kp, kx, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = () if n is None else (n,)
    params = (_params(kp, cfg) if n is None else
              jax.vmap(lambda k: _params(k, cfg))(jax.random.split(kp, n)))
    x = jax.random.normal(kx, lead + (batch, size, size, cfg.channels))
    y = jax.random.randint(ky, lead + (batch,), 0, cfg.n_classes)
    return params, x, y


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@functools.cache
def _compiled(mode, forward, grad):
    fn = (jax.grad(lambda p, x, y: xent_loss(forward(p, x), y)) if grad
          else lambda p, x, y: forward(p, x))
    return jax.jit(fn if mode == "single" else jax.vmap(fn))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("size", [4, 32])
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("mode", ["single", "population"])
def test_matches_plain_cnn(mode, batch, size, seed):
    params, x, y = _inputs(seed, batch, size,
                           n=None if mode == "single" else N_MULES)
    with jax.enable_x64(True), jax.default_matmul_precision("highest"):
        params, x = jax.tree.map(lambda a: a.astype(jnp.float64), (params, x))
        out = _compiled(mode, cnn_forward, False)(params, x, y)
        out_ref = _compiled(mode, plain_forward, False)(params, x, y)
        got = _compiled(mode, cnn_forward, True)(params, x, y)
        want = _compiled(mode, plain_forward, True)(params, x, y)
    assert _rel(out, out_ref) <= 1e-6
    for (path, leaf), got_leaf in zip(
            jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        assert np.linalg.norm(leaf) > 0, name
        assert _rel(got_leaf, leaf) <= 1e-5, (name, _rel(got_leaf, leaf))


@pytest.mark.parametrize("values", ["few_levels", "constant_windows"])
def test_pool_ties_route_like_select_and_scatter(values):
    """Inputs with ties in most windows: the gradient of the grouped pool
    is, bitwise, ``select-and-scatter``'s under space to depth."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    shape = (4, 8, 8, 32)
    if values == "few_levels":
        x = jax.random.randint(k1, shape, 0, 3).astype(jnp.float32)
    else:
        x = jnp.repeat(jnp.repeat(jax.random.normal(k1, (4, 4, 4, 32)), 2, 1),
                       2, 2)
    dy = jax.random.normal(k2, (4, 4, 4, 32))
    want = jax.grad(lambda x: jnp.sum(_pool(x) * dy))(x)
    got = jax.grad(lambda x: jnp.sum(_pool_groups(_space_to_depth(x)) * dy))(x)
    assert np.array_equal(_pool_groups(_space_to_depth(x)), _pool(x))
    assert np.array_equal(got, want)


def _expand_by_rule(w):
    """``W'[d, e, (p, q, c), (a, b, f)] = W[2d + p - a - 1, 2e + q - b - 1,
    c, f]`` where both lie in 0..2, else 0, by loops."""
    w = np.asarray(w)
    c, f = w.shape[2:]
    out = np.zeros((3, 3, 2, 2, c, 2, 2, f), w.dtype)
    for d, e, p, q, a, b in np.ndindex(3, 3, 2, 2, 2, 2):
        ky, kx = 2 * d + p - a - 1, 2 * e + q - b - 1
        if 0 <= ky < 3 and 0 <= kx < 3:
            out[d, e, p, q, :, a, b, :] = w[ky, kx]
    return out.reshape(3, 3, 4 * c, 4 * f)


def _dot_precisions(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            yield eqn.params["precision"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_precisions(sub)


def test_conv1_expansion_is_exact():
    """The expanded kernel holds conv1's weights bitwise, and nothing in its
    construction contracts below ``highest`` (on the TPU the default would
    round the weights to bf16 once more before the conv)."""
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 3, 32))
    assert np.array_equal(_expand_conv1(w), _expand_by_rule(w))
    highest = jax.lax.Precision.HIGHEST
    for precision in _dot_precisions(jax.make_jaxpr(_expand_conv1)(w).jaxpr):
        assert precision == (highest, highest), precision


@pytest.mark.parametrize("hw", [(5, 4), (4, 7)])
def test_odd_image_side_raises(hw):
    params = init_cnn(jax.random.PRNGKey(0), CNNConfig(image_size=4))
    with pytest.raises(ValueError, match="even"):
        cnn_forward(params, jnp.zeros((2,) + hw + (3,)))
