"""Plain reference of the mule CNN (paper Sec 4.2.1), its weights and data.

Straightforward JAX in the dtype it is given: no kernels, no batching
tricks, every contraction at the precision the caller passes (``highest``
for the reference, so float32 on the TPU is float32). It imports nothing
of the program. The parameter tree uses the program's leaf names, so the
benchmark can hand the same weights to both and compare leaf by leaf.

  conv 3x3 (32) -> batch norm -> ReLU -> max pool 2x2
  conv 3x3 (64) -> batch norm -> ReLU -> max pool 2x2
  flatten -> dense 128 -> ReLU -> dense 20
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg):
    """Weights from ``key``: normal at the configuration's scales, biases
    0, batch-norm scale 1 (float32)."""
    f1, f2 = cfg["conv_features"]
    k, c = cfg["kernel_size"], cfg["channels"]
    flat = (cfg["image_size"] // 4) ** 2 * f2
    s = cfg["init_scale"]
    ks = jax.random.split(key, 4)
    return {
        "conv1": s["conv1"] * jax.random.normal(ks[0], (k, k, c, f1)),
        "bn1": {"scale": jnp.ones((f1,)), "bias": jnp.zeros((f1,))},
        "conv2": s["conv2"] * jax.random.normal(ks[1], (k, k, f1, f2)),
        "bn2": {"scale": jnp.ones((f2,)), "bias": jnp.zeros((f2,))},
        "fc1": s["fc1"] * jax.random.normal(ks[2], (flat, cfg["hidden"])),
        "fc1_b": jnp.zeros((cfg["hidden"],)),
        "fc2": s["fc2"] * jax.random.normal(ks[3], (cfg["hidden"],
                                                    cfg["n_classes"])),
        "fc2_b": jnp.zeros((cfg["n_classes"],)),
    }


def _conv(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _bn(x, p):
    mu = x.mean(axis=(0, 1, 2), keepdims=True)
    var = ((x - mu) ** 2).mean(axis=(0, 1, 2), keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params, x, precision):
    """images [B, H, W, C] -> logits [B, n_classes]."""
    h = _pool(jnp.maximum(_bn(_conv(x, params["conv1"], precision),
                              params["bn1"]), 0))
    h = _pool(jnp.maximum(_bn(_conv(h, params["conv2"], precision),
                              params["bn2"]), 0))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(jnp.dot(h, params["fc1"], precision=precision)
                    + params["fc1_b"], 0)
    return jnp.dot(h, params["fc2"], precision=precision) + params["fc2_b"]


def make_data(key, cfg, data):
    """``data["per_class"]`` images of each class: a smooth per-class
    prototype (4x4x3 noise upsampled to the image size) plus pixel noise,
    normalized. Returns (x [N, H, W, C] float32, y [N] int32)."""
    n, size, c = cfg["n_classes"], cfg["image_size"], cfg["channels"]
    per = data["per_class"]
    kp, kn = jax.random.split(key)
    coarse = jax.random.normal(kp, (n, 4, 4, c))
    protos = jnp.repeat(jnp.repeat(coarse, size // 4, 1), size // 4, 2)
    x = protos[:, None] + data["noise"] * jax.random.normal(
        kn, (n, per, size, size, c))
    x = x.reshape(n * per, size, size, c)
    x = (x - x.mean()) / x.std()
    y = jnp.repeat(jnp.arange(n, dtype=jnp.int32), per)
    return x, y
