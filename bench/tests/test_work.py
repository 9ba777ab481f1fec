"""FLOP counts from the shapes against XLA's count of one forward pass at
published widths (CPU). XLA also counts the elementwise work (batch norm,
activations, pooling, LSTM gates) that ``work.py`` leaves out, so its count
lies a few percent above, never below."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import work
from spec import BENCH_DIR, load_module


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["mule-cnn", "mule-lstm-cnn"])
def test_forward_flops_against_xla(name):
    cfg = _config(name)
    ref = load_module(os.path.join(BENCH_DIR, "configs", name + ".py"))
    params = ref.init(jax.random.PRNGKey(0), cfg)
    assert sum(l.size for l in jax.tree.leaves(params)) \
        == cfg["params_per_mule"]
    shape = ((1, cfg["image_size"], cfg["image_size"], cfg["channels"])
             if "image_size" in cfg else (1, cfg["window"], cfg["channels"]))
    cost = jax.jit(lambda p, x: ref.forward(p, x, None)).lower(
        params, jnp.zeros(shape)).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = work.forward_flops(cfg)
    assert ours <= cost["flops"] <= 1.08 * ours


def test_counts_by_hand():
    assert work.taps(32, 3) == 94            # 32 * 3 minus two edge taps
    assert work.taps(128, 5, 2) == 317       # pad 1 low, 2 high
    cnn = _config("mule-cnn")
    macs = 94 ** 2 * 3 * 32 + 46 ** 2 * 32 * 64 + 4096 * 128 + 128 * 20
    assert work.forward_flops(cnn) == 2 * macs
    # backward: weight gradients of every layer, input gradients of all
    # but the first
    assert work.train_flops(cnn) == 6 * macs - 2 * 94 ** 2 * 3 * 32
    assert work.space_aggregation(8, 512, 10)["flops"] == 2 * 8 * 512 * 10
    assert work.encounter_mix(4, 10)["flops"] == 2 * 4 * 4 * 10
