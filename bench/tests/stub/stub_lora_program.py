"""The program side of the test-only adapter configuration: the model of
``stub-lora.py`` computed another way, at the default precision."""
import jax
import jax.numpy as jnp


def forward(params, x, shared):
    e = jax.nn.one_hot(x, shared["embed"].shape[0]) @ shared["embed"]
    a = jnp.tanh(e @ shared["w1"] + (e @ params["a1"]) @ params["b1"]
                 + shared["c1"])
    return (a @ shared["w2"] + (a @ params["a2"]) @ params["b2"]
            + shared["c2"])


def loss(logits, y):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[..., None], axis=-1))
