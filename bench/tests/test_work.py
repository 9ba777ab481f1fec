"""FLOP counts from the shapes against XLA's count of one forward pass
(CPU), for every configuration of ``BENCHMARK.json`` at its own widths and
the two test-only token configurations. XLA also counts the elementwise work
(batch norm, activations, pooling, LSTM gates) that the counts leave out,
so its count lies a few percent above, never below: at most 8% above,
unless the configuration states its own share (``xla_flops_over``) and the
reason (``xla_flops_over_why``)."""
import json
import os

import jax
import pytest

import work
from spec import BENCH_DIR, ROOT, load_module

STUB = os.path.join(BENCH_DIR, "tests", "stub")


def _json(path):
    with open(path) as f:
        return json.load(f)


def _configs():
    """(name, configuration file, its first cell's traffic file) of every
    configuration in ``BENCHMARK.json``, and of the test-only token one."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    out = []
    for c in bench["configs"]:
        traffic = next(w["traffic"] for w in bench["workloads"]
                       if w["config"] == c["name"])
        out.append((c["name"], os.path.join(ROOT, c["file"]),
                    os.path.join(BENCH_DIR, "traffic", traffic + ".json")))
    return out + [(name, os.path.join(STUB, name + ".json"),
                   os.path.join(STUB, "mlmule-commuter-m16.json"))
                  for name in ("stub-token", "stub-lora")]


CONFIGS = _configs()


@pytest.mark.parametrize("name,config,traffic", CONFIGS,
                         ids=[c[0] for c in CONFIGS])
def test_forward_flops_against_xla(name, config, traffic):
    """The example is one row of the configuration's own data, made as its
    first cell's traffic makes it; a configuration with a frozen base reads
    it as its last argument."""
    cfg = _json(config)
    ref = load_module(os.path.join(ROOT, cfg["reference"]))
    params = ref.init(jax.random.PRNGKey(0), cfg)
    assert sum(l.size for l in jax.tree.leaves(params)) \
        == cfg["params_per_mule"]
    x, _ = ref.make_data(jax.random.PRNGKey(0), cfg,
                         dict(_json(traffic)["data"], per_class=1))
    shared = (ref.init_shared(jax.random.PRNGKey(1), cfg),) \
        if hasattr(ref, "init_shared") else ()
    cost = jax.jit(lambda p, x, *s: ref.forward(p, x, None, *s)).lower(
        params, x[:1], *shared).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    over = cfg.get("xla_flops_over", 0.08)
    if "xla_flops_over" in cfg:
        assert cfg.get("xla_flops_over_why"), name
    ours = work.forward_flops(cfg, ref)
    assert ours <= cost["flops"] <= (1 + over) * ours


def test_counts_by_hand():
    assert work.taps(32, 3) == 94            # 32 * 3 minus two edge taps
    assert work.taps(128, 5, 2) == 317       # pad 1 low, 2 high
    cnn = _json(os.path.join(BENCH_DIR, "configs", "mule-cnn.json"))
    macs = 94 ** 2 * 3 * 32 + 46 ** 2 * 32 * 64 + 4096 * 128 + 128 * 20
    assert work.forward_flops(cnn) == 2 * macs
    # backward: weight gradients of every layer, input gradients of all
    # but the first
    assert work.train_flops(cnn) == 6 * macs - 2 * 94 ** 2 * 3 * 32
    assert work.space_aggregation(8, 512, 10)["flops"] == 2 * 8 * 512 * 10
    assert work.encounter_mix(4, 10)["flops"] == 2 * 4 * 4 * 10
