"""The program side of the test-only next-token configuration: the model of
``stub-token.py`` computed another way, at the default precision."""
import jax
import jax.numpy as jnp


def forward(params, x):
    e = jax.nn.one_hot(x, params["embed"].shape[0]) @ params["embed"]
    h = jnp.tanh(e @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def loss(logits, y):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[..., None], axis=-1))
