"""Plain reference of the mule LSTM-CNN (paper Sec 4.3.1), its weights and
data.

Straightforward JAX in the dtype it is given, every contraction at the
precision the caller passes; it imports nothing of the program. The
parameter tree uses the program's leaf names.

  conv1d k5 s2 (32) + bias -> ReLU -> conv1d k5 s2 (64) + bias -> ReLU
  LSTM 64 over the 32 remaining steps:
    i, f, g, o = split(x Wx + h Wh + b)
    c <- sigmoid(f + 1) c + sigmoid(i) tanh(g);  h <- sigmoid(o) tanh(c)
  dense 4 on the last h
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg):
    f1, f2 = cfg["conv_features"]
    k, h = cfg["kernel_size"], cfg["lstm_hidden"]
    s = cfg["init_scale"]
    ks = jax.random.split(key, 5)
    return {
        "conv1": s["conv1"] * jax.random.normal(ks[0],
                                                (k, cfg["channels"], f1)),
        "conv1_b": jnp.zeros((f1,)),
        "conv2": s["conv2"] * jax.random.normal(ks[1], (k, f1, f2)),
        "conv2_b": jnp.zeros((f2,)),
        "lstm_wx": s["lstm_wx"] * jax.random.normal(ks[2], (f2, 4 * h)),
        "lstm_wh": s["lstm_wh"] * jax.random.normal(ks[3], (h, 4 * h)),
        "lstm_b": jnp.zeros((4 * h,)),
        "fc": s["fc"] * jax.random.normal(ks[4], (h, cfg["n_classes"])),
        "fc_b": jnp.zeros((cfg["n_classes"],)),
    }


def _conv(x, w, b, precision):
    y = jax.lax.conv_general_dilated(
        x, w, (2,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"),
        precision=precision)
    return jnp.maximum(y + b, 0)


def _sigmoid(z):
    return 1 / (1 + jnp.exp(-z))


def forward(params, x, precision):
    """windows [B, T, C] -> logits [B, n_classes]."""
    h2 = _conv(_conv(x, params["conv1"], params["conv1_b"], precision),
               params["conv2"], params["conv2_b"], precision)
    hidden = params["lstm_wh"].shape[0]
    h = c = jnp.zeros((x.shape[0], hidden), x.dtype)
    for t in range(h2.shape[1]):
        gates = (jnp.dot(h2[:, t], params["lstm_wx"], precision=precision)
                 + jnp.dot(h, params["lstm_wh"], precision=precision)
                 + params["lstm_b"])
        i, f, g, o = (gates[:, k * hidden:(k + 1) * hidden]
                      for k in range(4))
        c = _sigmoid(f + 1) * c + _sigmoid(i) * jnp.tanh(g)
        h = _sigmoid(o) * jnp.tanh(c)
    return jnp.dot(h, params["fc"], precision=precision) + params["fc_b"]


def make_data(key, cfg, data):
    """``data["per_class"]`` windows of each activity: three sinusoids per
    channel at per-activity frequencies (0.5-8 Hz at 50 Hz) and amplitudes
    with a random phase per window, plus noise. Returns (x [N, T, C]
    float32, y [N] int32)."""
    n, t_len, c = cfg["n_classes"], cfg["window"], cfg["channels"]
    per = data["per_class"]
    kf, ka, kp, kn = jax.random.split(key, 4)
    freq = jax.random.uniform(kf, (n, 1, 1, c, 3), minval=0.5, maxval=8.0)
    amp = jax.random.uniform(ka, (n, 1, 1, c, 3), minval=0.3, maxval=1.2)
    phase = jax.random.uniform(kp, (n, per, 1, c, 3), maxval=2 * jnp.pi)
    t = (jnp.arange(t_len) / 50.0)[None, None, :, None, None]
    x = (amp * jnp.sin(2 * jnp.pi * freq * t + phase)).sum(-1)
    x = x + data["noise"] * jax.random.normal(kn, x.shape)
    y = jnp.repeat(jnp.arange(n, dtype=jnp.int32), per)
    return x.reshape(n * per, t_len, c), y
