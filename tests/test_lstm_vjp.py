"""The LSTM-CNN's hand-written LSTM backward.

``repro.models.cnn`` gives the HAR model's recurrence a ``jax.custom_vjp``:
the forward hoists ``xs Wx + b`` out of the time loop and saves two stacks
(``[h_{t-1}, c_{t-1}]`` and the four gate activations), and the backward is a
reverse scan that carries only ``(dh, dc)`` and emits each step's gate
gradient, with the weight, bias and input gradients contracted once after
the loop. Pinned here against autodiff of a plain ``lax.scan`` LSTM kept in
this file, for one model, a vmapped population and a population under
``shard_map``, and structurally: no loop of the vmapped gradient carries a
weight-shaped array, and the forward loop emits at most three stacks.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.mule_lstm_cnn import LSTMCNNConfig
from repro.models.cnn import (_conv1d, init_lstm_cnn, lstm_cnn_forward,
                              xent_loss)

CFG = LSTMCNNConfig()
N_MULES = 3


def plain_forward(params, x):
    """The LSTM-CNN with its recurrence left to autodiff."""
    h1 = jax.nn.relu(_conv1d(x, params["conv1"], params["conv1_b"], 2))
    h2 = jax.nn.relu(_conv1d(h1, params["conv2"], params["conv2_b"], 2))

    def step(carry, xt):
        h, c = carry
        z = xt @ params["lstm_wx"] + h @ params["lstm_wh"] + params["lstm_b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        return (jax.nn.sigmoid(o) * jnp.tanh(c), c), None

    h0 = jnp.zeros((x.shape[0], params["lstm_wh"].shape[0]))
    (h, _), _ = jax.lax.scan(step, (h0, h0), jnp.moveaxis(h2, 1, 0))
    return h @ params["fc"] + params["fc_b"]


def _inputs(seed, batch, cfg=CFG, n=None):
    """One model (``n`` None) or ``n`` stacked models, with their windows
    and labels."""
    kp, kx, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = () if n is None else (n,)
    if n is None:
        params = init_lstm_cnn(kp, cfg)
    else:
        params = jax.vmap(lambda k: init_lstm_cnn(k, cfg))(
            jax.random.split(kp, n))
    x = jax.random.normal(kx, lead + (batch, cfg.window, cfg.channels))
    y = jax.random.randint(ky, lead + (batch,), 0, cfg.n_classes)
    return params, x, y


def _grad_fn(forward):
    return jax.grad(lambda p, x, y: xent_loss(forward(p, x), y))


def _on_mesh(fn):
    """``fn`` over the mule axis of a mesh of the suite's devices."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("mule",))
    return jax.shard_map(fn, mesh=mesh, in_specs=P("mule"),
                         out_specs=P("mule"), check_vma=False)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@functools.cache
def _compiled(mode, forward, grad):
    """The jitted forward or gradient of ``forward`` for one mode."""
    fn = _grad_fn(forward) if grad else forward
    return jax.jit({"single": fn, "population": jax.vmap(fn),
                    "mesh": _on_mesh(jax.vmap(fn))}[mode])


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("batch", [1, 12])
@pytest.mark.parametrize("mode", ["single", "population", "mesh"])
def test_gradients_match_autodiff(mode, batch, seed):
    params, x, y = _inputs(seed, batch, n=None if mode == "single"
                           else N_MULES)
    with jax.default_matmul_precision("highest"):
        got = _compiled(mode, lstm_cnn_forward, True)(params, x, y)
        want = _compiled(mode, plain_forward, True)(params, x, y)
        out = _compiled(mode, lstm_cnn_forward, False)(params, x)
        out_ref = _compiled(mode, plain_forward, False)(params, x)
    assert _rel(out, out_ref) <= 1e-6
    for name in want:
        assert float(jnp.linalg.norm(want[name])) > 0, name
        assert _rel(got[name], want[name]) <= 1e-5, name


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_vmapped_gradient_loops_carry_no_weights():
    # widths chosen so Wx [32, 96] and Wh [24, 96] differ in shape
    cfg = LSTMCNNConfig(window=32, conv_features=(16, 32), lstm_hidden=24)
    params, x, y = _inputs(0, 12, cfg, n=N_MULES)
    grad = jax.vmap(_grad_fn(lstm_cnn_forward))
    scans = list(_scans(jax.make_jaxpr(grad)(params, x, y).jaxpr))
    weights = {params["lstm_wx"].shape[1:], params["lstm_wh"].shape[1:]}
    forward = [e for e in scans if not e.params["reverse"]]
    backward = [e for e in scans if e.params["reverse"]]
    assert forward and backward
    for eqn in scans:
        k, n = eqn.params["num_consts"], eqn.params["num_carry"]
        for v in eqn.invars[k:k + n]:
            assert tuple(v.aval.shape[-2:]) not in weights, v.aval
    for eqn in forward:
        assert len(eqn.outvars) - eqn.params["num_carry"] <= 3
