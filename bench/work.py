"""The work a step requires, counted from the shapes: FLOPs of the mule
models, and FLOPs and bytes of the two exchange contractions, so that a
later kernel's roofline reads the same work whichever implementation runs.

Model FLOPs count the contractions (2 per multiply-add) of convolutions,
dense layers and LSTM gates; elementwise work (normalization, activations,
pooling) is left out, as it is in the usual MFU, and so are the taps of a
"SAME" convolution that fall on its zero padding. Training one example is
the forward pass, the weight gradient of every layer (as much again), and
the input gradient of every layer but the first, whose input is data.

A configuration this file does not count states its own: its reference
file (the configuration's ``reference``) defines ``forward_flops(cfg)`` and
``train_flops(cfg)``, FLOPs of one example by the same rules, and every
count here takes that module as ``ref``.

Frozen weights (a reference file's ``init_shared``) take no weight
gradient. Training one example over a frozen base is the forward pass, the
weight gradient of every contraction with a trained weight (an adapter),
and the input gradient of every contraction whose input depends on a
trained leaf: what autodiff with respect to the trained leaves computes.
Past the first adapter that is every later layer's input gradient, so a
deep model over a frozen base costs about forward plus input gradient,
twice its forward pass.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def taps(n: int, k: int, stride: int = 1) -> int:
    """Kernel taps inside the input, summed over the outputs of a "SAME"
    convolution of a length-``n`` axis (padding split low-first as XLA
    does)."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(sum(0 <= i * stride - lo + j < n for j in range(k))
               for i in range(out))


def _cnn_layers(cfg: Dict) -> List[Tuple[str, int]]:
    """(layer, multiply-adds per example), input layer first."""
    s, c, k = cfg["image_size"], cfg["channels"], cfg["kernel_size"]
    f1, f2 = cfg["conv_features"]
    return [("conv1", taps(s, k) ** 2 * c * f1),
            ("conv2", taps(s // 2, k) ** 2 * f1 * f2),
            ("fc1", (s // 4) ** 2 * f2 * cfg["hidden"]),
            ("fc2", cfg["hidden"] * cfg["n_classes"])]


def _lstm_cnn_layers(cfg: Dict) -> List[Tuple[str, int]]:
    w, c, k, st = (cfg["window"], cfg["channels"], cfg["kernel_size"],
                   cfg["stride"])
    f1, f2 = cfg["conv_features"]
    h = cfg["lstm_hidden"]
    t1 = -(-w // st)
    t2 = -(-t1 // st)
    return [("conv1", taps(w, k, st) * c * f1),
            ("conv2", taps(t1, k, st) * f1 * f2),
            ("lstm", t2 * (f2 + h) * 4 * h),
            ("fc", h * cfg["n_classes"])]


def layers(cfg: Dict) -> List[Tuple[str, int]]:
    if "image_size" in cfg:
        return _cnn_layers(cfg)
    if "lstm_hidden" in cfg:
        return _lstm_cnn_layers(cfg)
    raise ValueError(f"no FLOP count for configuration {cfg.get('name')!r}: "
                     f"its reference file defines no forward_flops(cfg) "
                     f"and train_flops(cfg)")


def forward_flops(cfg: Dict, ref=None) -> int:
    """FLOPs of one example's forward pass."""
    if hasattr(ref, "forward_flops"):
        return int(ref.forward_flops(cfg))
    return 2 * sum(macs for _, macs in layers(cfg))


def train_flops(cfg: Dict, ref=None) -> int:
    """FLOPs of one example's forward and backward pass."""
    if hasattr(ref, "train_flops"):
        return int(ref.train_flops(cfg))
    ls = layers(cfg)
    fwd = 2 * sum(m for _, m in ls)
    return fwd + fwd + (fwd - 2 * ls[0][1])


def step_train_flops(cfg: Dict, trained_mules: int, ref=None) -> int:
    """FLOPs a step requires: the kept mules, each on its batch."""
    return trained_mules * cfg["batch"] * train_flops(cfg, ref)


def space_aggregation(n_fixed: int, n_mules: int, d: int) -> Dict[str, int]:
    """[F, M] x [M, D] weighted mean of the delivered models (f32)."""
    return {"flops": 2 * n_fixed * n_mules * d,
            "bytes": 4 * (n_mules * d + n_fixed * n_mules + n_fixed * d)}


def encounter_mix(n_mules: int, d: int) -> Dict[str, int]:
    """[M, M] x [M, D] mean over encountered peers (f32)."""
    return {"flops": 2 * n_mules * n_mules * d,
            "bytes": 4 * (2 * n_mules * d + n_mules * n_mules)}
