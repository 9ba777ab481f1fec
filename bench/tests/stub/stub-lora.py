"""Plain reference of a tiny next-token model trained as phones adapt a
language model: per-mule low-rank adapters (LoRA, arXiv:2106.09685) over a
frozen base that every mule shares, a configuration the harness knows by
its files alone (``bench/tests/test_frozen_base.py``).

  base (frozen, ``init_shared``): embedding E [V, D], dense W1 [D, H] + c1,
    dense W2 [H, V] + c2;
  adapters (per mule, ``init``): A1 [D, R], B1 [R, H], A2 [H, R], B2 [R, V];
  token ids [B, T] -> e = E[x] -> a = tanh(e W1 + (e A1) B1 + c1)
    -> logits a W2 + (a A2) B2 + c2 [B, T, V];
  loss: mean cross-entropy of each position against the next token.

LoRA starts B at zero; here B is drawn small, so that every adapter leaf
moves from the first step and is compared. It imports nothing of the
program side (``stub_lora_program.py``), which computes the same model
another way (one-hot contraction, log-softmax).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_shared(key, cfg):
    """The frozen base: built once from the seed, trained by no mule."""
    v, d, h = cfg["vocab"], cfg["embed"], cfg["hidden"]
    s = cfg["init_scale"]
    ke, k1, k2, kc1, kc2 = jax.random.split(key, 5)
    return {"embed": s["embed"] * jax.random.normal(ke, (v, d)),
            "w1": s["w1"] * jax.random.normal(k1, (d, h)),
            "c1": s["bias"] * jax.random.normal(kc1, (h,)),
            "w2": s["w2"] * jax.random.normal(k2, (h, v)),
            "c2": s["bias"] * jax.random.normal(kc2, (v,))}


def init(key, cfg):
    """One mule's trainable leaves: the adapters of both dense layers."""
    v, d, h, r = cfg["vocab"], cfg["embed"], cfg["hidden"], cfg["rank"]
    s = cfg["init_scale"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"a1": s["lora_a"] * jax.random.normal(k1, (d, r)),
            "b1": s["lora_b"] * jax.random.normal(k2, (r, h)),
            "a2": s["lora_a"] * jax.random.normal(k3, (h, r)),
            "b2": s["lora_b"] * jax.random.normal(k4, (r, v))}


def forward(params, x, precision, shared):
    """token ids [B, T] -> logits [B, T, V]."""
    dot = lambda a, b: jnp.dot(a, b, precision=precision)
    e = jnp.take(shared["embed"], x, axis=0)
    a = jnp.tanh(dot(e, shared["w1"]) + dot(dot(e, params["a1"]),
                                            params["b1"]) + shared["c1"])
    return (dot(a, shared["w2"]) + dot(dot(a, params["a2"]), params["b2"])
            + shared["c2"])


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


def _adapter_macs(cfg):
    """Multiply-adds of the four adapter contractions, one position."""
    d, h, v, r = cfg["embed"], cfg["hidden"], cfg["vocab"], cfg["rank"]
    return r * (d + h) + r * (h + v)


def forward_flops(cfg):
    """The two frozen contractions and the four adapter ones at every
    position; the gather counts none."""
    d, h, v = cfg["embed"], cfg["hidden"], cfg["vocab"]
    return 2 * cfg["seq"] * (d * h + h * v + _adapter_macs(cfg))


def train_flops(cfg):
    """By ``work.py``'s rule for frozen weights: the forward pass; the
    weight gradients of the four adapter contractions (none of the frozen
    ones); and the input gradients of the contractions whose input depends
    on an adapter: e B1's input e A1, and all three of the second layer's
    (a W2, a A2, (a A2) B2). e W1 and e A1 read the frozen embedding and
    need none."""
    h, v, r = cfg["hidden"], cfg["vocab"], cfg["rank"]
    input_grads = r * h + h * v + h * r + r * v
    return (forward_flops(cfg)
            + 2 * cfg["seq"] * (_adapter_macs(cfg) + input_grads))
