"""The paper's task models.

- ``CNN`` (Sec 4.2.1): two conv blocks (3x3 conv, batch norm, ReLU, 2x2 max
  pool) + a two-layer FC classifier — CIFAR-100 super-class task.
- ``LSTM-CNN`` (Sec 4.3.1, Xia et al. 2020): two strided 1-D conv blocks over
  the IMU window followed by an LSTM and a dense classifier — HAR task.

Batch norm uses in-batch statistics (no running stats); in federated
simulations the learned scale/bias are part of the exchanged model, which is
the common convention in FL research on small CNNs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.mule_cnn import CNNConfig
from repro.configs.mule_lstm_cnn import LSTMCNNConfig
from repro.models.layers import dense_init


# ---------------------------------------------------------------------------
# CNN (image classification)
# ---------------------------------------------------------------------------


def init_cnn(key, cfg: CNNConfig):
    f1, f2 = cfg.conv_features
    ks = jax.random.split(key, 4)
    flat = (cfg.image_size // 4) * (cfg.image_size // 4) * f2
    return {
        "conv1": dense_init(ks[0], (3, 3, cfg.channels, f1), scale=0.1),
        "bn1": {"scale": jnp.ones((f1,)), "bias": jnp.zeros((f1,))},
        "conv2": dense_init(ks[1], (3, 3, f1, f2), scale=0.1),
        "bn2": {"scale": jnp.ones((f2,)), "bias": jnp.zeros((f2,))},
        "fc1": dense_init(ks[2], (flat, cfg.hidden), scale=0.05),
        "fc1_b": jnp.zeros((cfg.hidden,)),
        "fc2": dense_init(ks[3], (cfg.hidden, cfg.n_classes), scale=0.05),
        "fc2_b": jnp.zeros((cfg.n_classes,)),
    }


def _conv2d(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


# Block 1 runs pool-window-major: its activations are [B, H/2, W/2, 4*C],
# the four pixels (py, px) of each 2x2 pool window, in row-major order, as
# four groups of C channels on the minor axis. At C = 32 that axis is 128
# lanes wide; in NHWC the 32 channels would fill a quarter of the 128 lanes,
# and every pass over the full-resolution tensors would move four times
# their bytes.


def _space_to_depth(x):
    """[B, H, W, C] -> [B, H/2, W/2, 4C], channels ordered (py, px, c)."""
    b, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(f"the image mule's 2x2 pool needs even image sides, "
                         f"got {h}x{w}")
    x = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // 2, w // 2, 4 * c)


# Row ``ky + 2`` of the 3x3 kernel padded by two on each side that tap
# ``d`` of the space-to-depth conv applies from input pixel row ``p`` to
# output pixel row ``a``: full-resolution row ``2i + a + ky - 1`` is block
# row ``i + d - 1``, pixel row ``p``, so ``ky = 2(d - 1) + p - a + 1``.
_TAPS = tuple(2 * d + p - a + 1 for d in range(3) for p in range(2)
              for a in range(2))


def _expand_conv1(w):
    """A [3, 3, C, F] kernel as the [3, 3, 4C, 4F] kernel that applies it
    between space-to-depth layouts: ``W'[d, e, (p, q, c), (a, b, f)] =
    W[2d + p - a - 1, 2e + q - b - 1, c, f]`` where both indices lie in
    0..2, else 0. Exact data movement (pad, slice, stack): no weight is
    rounded."""
    c, f = w.shape[2:]
    wp = jnp.pad(w, ((2, 2), (2, 2), (0, 0), (0, 0)))
    rows = jnp.stack([wp[i] for i in _TAPS])               # [(d,p,a), 7, C, F]
    taps = jnp.stack([rows[:, i] for i in _TAPS], axis=1)  # [(d,p,a), (e,q,b)]
    taps = taps.reshape(3, 2, 2, 3, 2, 2, c, f).transpose(0, 3, 1, 4, 6, 2, 5, 7)
    return taps.reshape(3, 3, 4 * c, 4 * f)


def _bn_groups(x, p, eps=1e-5):
    """``_bn`` over a [..., 4C] pool-window-major activation: statistics per
    channel over the batch, the positions and the four groups. The
    statistics fold the groups after the lane-wide reductions, so no
    [..., 4, C] view of the activation is formed."""
    per_group = lambda v: jnp.tile(v.reshape(4, -1).mean(0), 4)
    d = x - per_group(jnp.mean(x, axis=(0, 1, 2)))
    var = per_group(jnp.mean(d * d, axis=(0, 1, 2)))
    return (d * jax.lax.rsqrt(var + eps) * jnp.tile(p["scale"], 4)
            + jnp.tile(p["bias"], 4))


def _group_max(x):
    """The 2x2 max pool of a [..., 4C] pool-window-major activation: the
    maximum over its four groups, [..., C]."""
    g = jnp.split(x, 4, axis=-1)
    return jnp.maximum(jnp.maximum(g[0], g[1]), jnp.maximum(g[2], g[3]))


_pool_groups = jax.custom_vjp(_group_max)


def _pool_groups_fwd(x):
    return _group_max(x), x


def _pool_groups_bwd(x, dy):
    """Each window's gradient to its first maximum in row-major order, as
    ``select-and-scatter`` routes the gradient of ``_pool``; JAX's own
    gradient of a max would split ties. The first maximum's group is one
    index per channel, and the gradient one select over all 4C lanes:
    routed group by group, each group's [..., C] piece and mask would be
    kept in memory, a quarter-filled lane row each."""
    g = jnp.split(x, 4, axis=-1)
    y = _group_max(x)
    first = jnp.where(g[0] == y, 0, jnp.where(g[1] == y, 1,
                                              jnp.where(g[2] == y, 2, 3)))
    group = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) \
        // dy.shape[-1]
    tile = lambda a: jnp.concatenate([a] * 4, axis=-1)
    return (jnp.where(tile(first) == group, tile(dy), 0.0),)


_pool_groups.defvjp(_pool_groups_fwd, _pool_groups_bwd)


def cnn_forward(params, images):
    """images: [B, H, W, C], H and W even -> logits [B, n_classes]."""
    x = _conv2d(_space_to_depth(images), _expand_conv1(params["conv1"]))
    x = _pool_groups(jax.nn.relu(_bn_groups(x, params["bn1"])))
    x = _pool(jax.nn.relu(_bn(_conv2d(x, params["conv2"]), params["bn2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["fc1_b"])
    return x @ params["fc2"] + params["fc2_b"]


# ---------------------------------------------------------------------------
# LSTM-CNN (IMU HAR)
# ---------------------------------------------------------------------------


def init_lstm_cnn(key, cfg: LSTMCNNConfig):
    f1, f2 = cfg.conv_features
    h = cfg.lstm_hidden
    ks = jax.random.split(key, 6)
    return {
        "conv1": dense_init(ks[0], (5, cfg.channels, f1), scale=0.1),
        "conv1_b": jnp.zeros((f1,)),
        "conv2": dense_init(ks[1], (5, f1, f2), scale=0.1),
        "conv2_b": jnp.zeros((f2,)),
        "lstm_wx": dense_init(ks[2], (f2, 4 * h), scale=0.08),
        "lstm_wh": dense_init(ks[3], (h, 4 * h), scale=0.08),
        "lstm_b": jnp.zeros((4 * h,)),
        "fc": dense_init(ks[4], (h, cfg.n_classes), scale=0.05),
        "fc_b": jnp.zeros((cfg.n_classes,)),
    }


def _conv1d(x, w, b, stride):
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC"))
    return out + b


def _lstm_project(xs, wx, b):
    """``xs Wx + b`` for every step at once: one [T*B, F] x [F, 4H]
    contraction whose rows run time-major, so each step's rows lie
    together."""
    t, batch, f = xs.shape
    return (xs.reshape(t * batch, f) @ wx + b).reshape(t, batch, -1)


def _lstm_last_h_fwd(xs, wx, wh, b):
    """The recurrence over ``xs`` [T, B, F] from zero state: the final
    hidden state and what the backward pass reads, ``[h_{t-1}, c_{t-1}]``
    stacked as one [T, B, 2H] and the four gate activations as one
    [T, B, 4H]. ``h`` and ``c`` share a stack so that each trip writes one
    lane-dense row (2H = 128 at the paper's widths): saved alone, ``h`` was
    kept in the layout of the ``dWh`` contraction and cost a strided write
    on every trip."""
    def step(carry, xw_t):
        h, c = carry
        i, f, g, o = jnp.split(xw_t + h @ wh, 4, axis=-1)
        i, f, g, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jnp.tanh(g),
                      jax.nn.sigmoid(o))
        c_new = f * c + i * g
        return (o * jnp.tanh(c_new), c_new), (
            jnp.concatenate([h, c], -1), jnp.concatenate([i, f, g, o], -1))

    h0 = jnp.zeros(xs.shape[1:-1] + wh.shape[:1], xs.dtype)
    (h, _), (hc, acts) = jax.lax.scan(step, (h0, h0),
                                      _lstm_project(xs, wx, b))
    return h, (xs, wx, wh, hc, acts)


@jax.custom_vjp
def _lstm_last_h(xs, wx, wh, b):
    """Final hidden state of the LSTM over ``xs`` [T, B, F]; the stacks the
    forward keeps for the backward are dead code here."""
    return _lstm_last_h_fwd(xs, wx, wh, b)[0]


def _lstm_last_h_bwd(res, dh):
    """A reverse loop that carries only ``(dh, dc)`` and emits each step's
    gate gradient ``dz``; the weight, bias and input gradients are then one
    contraction each over time and batch, outside the loop."""
    xs, wx, wh, hc, acts = res
    hidden = wh.shape[0]

    def step(carry, saved):
        dh, dc = carry
        hc_t, a = saved
        c = hc_t[..., hidden:]
        i, f, g, o = jnp.split(a, 4, axis=-1)
        tc = jnp.tanh(f * c + i * g)
        dc = dc + dh * o * (1.0 - tc * tc)
        dz = jnp.concatenate([dc * g * i * (1.0 - i),
                              dc * c * f * (1.0 - f),
                              dc * i * (1.0 - g * g),
                              dh * tc * o * (1.0 - o)], -1)
        return (dz @ wh.T, dc * f), dz

    _, dz = jax.lax.scan(step, (dh, jnp.zeros_like(dh)), (hc, acts),
                         reverse=True)
    return (dz @ wx.T, jnp.einsum("tbf,tbg->fg", xs, dz),
            jnp.einsum("tbh,tbg->hg", hc[..., :hidden], dz),
            dz.sum(axis=(0, 1)))


_lstm_last_h.defvjp(_lstm_last_h_fwd, _lstm_last_h_bwd)


def lstm_cnn_forward(params, x):
    """x: [B, T, C] IMU window -> logits [B, n_classes]."""
    h1 = jax.nn.relu(_conv1d(x, params["conv1"], params["conv1_b"], 2))
    h2 = jax.nn.relu(_conv1d(h1, params["conv2"], params["conv2_b"], 2))
    h = _lstm_last_h(jnp.moveaxis(h2, 1, 0), params["lstm_wx"],
                     params["lstm_wh"], params["lstm_b"])
    return h @ params["fc"] + params["fc_b"]


# ---------------------------------------------------------------------------
# shared loss / metric helpers
# ---------------------------------------------------------------------------


def xent_loss(logits, labels):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=-1))


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
