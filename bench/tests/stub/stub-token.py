"""Plain reference of a tiny next-token model, a configuration the harness
knows by its files alone (``bench/tests/test_token_config.py``).

  token ids [B, T] -> embedding (gather) -> dense + tanh -> dense to the
  vocabulary: logits [B, T, V]; loss: mean cross-entropy of each position
  against the next token.

It imports nothing of the program side (``stub_program.py``), which
computes the same model another way (one-hot contraction, log-softmax).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg):
    v, d, h = cfg["vocab"], cfg["embed"], cfg["hidden"]
    s = cfg["init_scale"]
    ke, k1, k2 = jax.random.split(key, 3)
    return {"embed": s["embed"] * jax.random.normal(ke, (v, d)),
            "w1": s["w1"] * jax.random.normal(k1, (d, h)),
            "b1": jnp.zeros((h,)),
            "w2": s["w2"] * jax.random.normal(k2, (h, v)),
            "b2": jnp.zeros((v,))}


def forward(params, x, precision):
    """token ids [B, T] -> logits [B, T, V]."""
    e = jnp.take(params["embed"], x, axis=0)
    h = jnp.tanh(jnp.dot(e, params["w1"], precision=precision) + params["b1"])
    return jnp.dot(h, params["w2"], precision=precision) + params["b2"]


def loss(logits, y):
    """Mean cross-entropy over every position: logits [B, T, V], next
    tokens [B, T]."""
    z = logits - logits.max(-1, keepdims=True)
    logp = z - jnp.log(jnp.exp(z).sum(-1, keepdims=True))
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()


def make_data(key, cfg, data):
    """``data["per_class"]`` sequences of each group: a group steps through
    the vocabulary by its own stride from a random start, and a share
    ``data["noise"]`` of tokens is replaced at random. Returns (x [N, T]
    int32, y [N, T] int32: the next tokens)."""
    n, v, t = cfg["n_classes"], cfg["vocab"], cfg["seq"]
    per = data["per_class"]
    ks, k0, kf, kr = jax.random.split(key, 4)
    stride = jax.random.randint(ks, (n, 1, 1), 1, v)
    start = jax.random.randint(k0, (n, per, 1), 0, v)
    toks = (start + stride * jnp.arange(t + 1)) % v
    flip = jax.random.bernoulli(kf, data["noise"], toks.shape)
    toks = jnp.where(flip, jax.random.randint(kr, toks.shape, 0, v), toks)
    toks = toks.astype(jnp.int32).reshape(n * per, t + 1)
    return toks[:, :-1], toks[:, 1:]


def forward_flops(cfg):
    """The two contractions of every position; the gather counts none."""
    return 2 * cfg["seq"] * (cfg["embed"] * cfg["hidden"]
                             + cfg["hidden"] * cfg["vocab"])


def train_flops(cfg):
    """Forward, the weight gradients of both contractions, and the input
    gradients of both: the embedding is trained, so the first
    contraction's input gradient is needed too."""
    return 3 * forward_flops(cfg)
