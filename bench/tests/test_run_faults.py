"""A whole run on the CPU at a small size: ``correct`` holds for the program
as it is, and comes out false with the timed path broken underneath, once
for each fault a cell can have:

- a step that returns its state unchanged;
- half of each batch left out, the mean taken over the rest;
- an answer altered where it is produced (the spaces' aggregate, or the
  peers' mix, handed to the wrong receiver; on a mesh, the reduced space
  payload);
- on a mule mesh, the exchange between chips left out (each chip reduces
  its own mules only).

The harness's look for a chip is skipped; the limits are the cell's own.
The models are cut to small widths so that the CPU runs in seconds; what
the runs compare is the same. The mule mesh's path (the traffic file kept
for a four-chip cell) runs on four host devices (``conftest.py``), with the
one-chip LSTM cell's limits.
"""
import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

import program
import run as bench_run
import spec
from spec import ROOT, load_cell

SMALL = {"mule-cnn": {"image_size": 8, "conv_features": [4, 8], "hidden": 16,
                      "n_classes": 4},
         "mule-lstm-cnn": {"window": 32, "conv_features": [4, 8],
                           "lstm_hidden": 8}}


MESH = "lstm-mlmule-commuter-x4"


def _load(name):
    """A cell of ``BENCHMARK.json``, or the mule mesh's path."""
    if name != MESH:
        return load_cell(name)
    cell = load_cell("lstm-mlmule-commuter")
    with open(os.path.join(spec.BENCH_DIR, "traffic",
                           "mlmule-commuter-m8192-x4.json")) as f:
        traffic = json.load(f)
    # the distributed engine keeps an age histogram, not the ring
    limits = {k: v for k, v in cell.limits.items()
              if k not in ("fresh_ages", "window_fresh_ages")}
    return dataclasses.replace(cell, name=MESH, chips=4, traffic=traffic,
                               limits=limits)


def small_cell(name):
    cell = _load(name)
    cell.config = dict(copy.deepcopy(cell.config),
                       **SMALL[cell.config["name"]])
    cell.traffic = dict(copy.deepcopy(cell.traffic), mules=16)
    cell.traffic["data"] = dict(cell.traffic["data"], per_class=8)
    cell.traffic.pop("reference_block", None)
    return cell


def _run(cell, monkeypatch, tmp_path, train_fn=None, trace=0):
    from repro.scenarios import jit_cache_clear
    jit_cache_clear()
    monkeypatch.setattr(bench_run, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench_run, "_check_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    if train_fn is not None:
        real = program.make_program
        monkeypatch.setattr(program, "make_program", lambda c, inputs:
                            dataclasses.replace(real(c, inputs),
                                                train_fn=train_fn))
    out = bench_run.run(["--workload", cell.name, "--seed", "2147483659",
                         "--seconds", "0.1", "--trace", str(trace)],
                        log=lambda *a, **k: None)
    json.dumps(out)
    return out


def _half_batch_sgd(cell):
    from program import _resolve
    fwd = _resolve(cell.config["program"]["forward"])
    loss = _resolve(cell.config["program"]["loss"])
    lr = cell.config["lr"]

    def sgd(params, batch, key):
        xb, yb = batch
        h = xb.shape[0] // 2
        g = jax.grad(lambda p: loss(fwd(p, xb[:h]), yb[:h]))(params)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    return sgd


def _unchanged_step(monkeypatch):
    import repro.core.distributed as dist
    import repro.core.method_program as mp
    unchanged = lambda *a, **k: (lambda st, info, b, key: st)
    monkeypatch.setattr(mp, "compile_step", unchanged)
    monkeypatch.setattr(dist, "make_distributed_method_step", unchanged)


def _altered_answer(monkeypatch, method, mesh):
    if mesh:
        import repro.core.distributed as dist
        real_psum = dist.ordered_psum
        monkeypatch.setattr(dist, "ordered_psum", lambda x, axis_name: jnp.roll(
            real_psum(x, axis_name), 1, axis=0))
    elif method == "mlmule":
        import repro.core.population as pop
        real = pop.masked_group_mean

        def rolled(models, assign, **kw):
            agg, mass = real(models, assign, **kw)
            return jax.tree.map(lambda l: jnp.roll(l, 1, axis=0), agg), mass
        monkeypatch.setattr(pop, "masked_group_mean", rolled)
    else:
        import repro.baselines.gossip as gossip
        real = gossip.encounter_mix

        def rolled(*a, **kw):
            mix, mass = real(*a, **kw)
            return jnp.roll(mix, 1, axis=0), mass
        monkeypatch.setattr(gossip, "encounter_mix", rolled)


def _exchange_left_out(monkeypatch):
    import repro.core.distributed as dist
    monkeypatch.setattr(dist, "ordered_psum", lambda x, axis_name: x)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
PATHS = CELLS + [MESH]
FAULTS = [(name, fault) for name in PATHS
          for fault in ("unchanged", "half_batch", "altered")
          + (("exchange",) if _load(name).traffic.get("mesh") else ())]


@pytest.mark.parametrize("name", PATHS)
def test_sound_run_is_correct(name, monkeypatch, tmp_path):
    out = _run(small_cell(name), monkeypatch, tmp_path)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_is_not_correct(name, fault, monkeypatch, tmp_path):
    cell = small_cell(name)
    train_fn = None
    if fault == "unchanged":
        _unchanged_step(monkeypatch)
    elif fault == "half_batch":
        train_fn = _half_batch_sgd(cell)
    elif fault == "exchange":
        _exchange_left_out(monkeypatch)
    else:
        _altered_answer(monkeypatch, cell.traffic["method"]["name"],
                        cell.traffic.get("mesh"))
    out = _run(cell, monkeypatch, tmp_path, train_fn)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_no_tpu_refuses(monkeypatch):
    with pytest.raises(bench_run.NoChip):
        bench_run.run(["--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1"], log=lambda *a, **k: None)
