"""The trace reduction, checked by independent hand computations: on a
stretch recorded from a TPU v5e trace of ``cnn-mlmule-commuter`` (nested
loop and conditional operations, training and exchange operations), and on
cases written out by hand for the exposed collective time: two chips, and a
collective inside a loop."""
import json
import os

import numpy as np
import pytest

import devtrace as tr
from spec import BENCH_DIR, load_module

metrics = {name: load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))
           for name in ("idle_share", "mfu", "train_ms_per_step",
                        "exchange_ms_per_step",
                        "collective_exposed_ms_per_step")}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH_DIR, "data",
                           "trace-cnn-mlmule-commuter.json")) as f:
        return json.load(f)["events"]


def _direct_leaves(ops):
    """Operations with no other inside, by explicit containment (O(n^2),
    independent of the stack walk)."""
    def inside(a, b):  # a strictly nested in b
        return (a is not b and b["start"] <= a["start"]
                and a["start"] + a["dur"] <= b["start"] + b["dur"]
                and (a["dur"] < b["dur"] or a["start"] > b["start"]))
    return [b for b in ops if not any(inside(a, b) for a in ops)]


def _grid(ops, t0, t1):
    grid = np.zeros(int(t1 - t0) + 1, bool)
    for e in ops:
        grid[int(e["start"] - t0):int(e["start"] + e["dur"] - t0)] = True
    return grid


def test_recorded_busy_and_split(recorded):
    ops = [e for e in recorded if e["chip"] == 0]
    assert any(tr.is_train(e["scope"]) for e in ops)
    s = tr.summarize(recorded, steps=2)
    t0 = min(e["start"] for e in ops)
    t1 = max(e["start"] + e["dur"] for e in ops)
    leaves = _direct_leaves(ops)
    # the recorded stretch holds loops around their bodies' operations
    assert len(leaves) < len(ops)
    grid = _grid(leaves, t0, t1)
    assert grid.sum() < _grid(ops, t0, t1).sum()
    assert s["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert s["busy_s"] == pytest.approx(grid.sum() * 1e-9, rel=1e-6)
    train = sum(e["dur"] for e in leaves if tr.is_train(e["scope"]))
    other = sum(e["dur"] for e in leaves if not tr.is_train(e["scope"]))
    assert s["train_s"] == pytest.approx(train * 1e-9, rel=1e-9)
    assert s["exchange_s"] == pytest.approx(other * 1e-9, rel=1e-9)
    # one chip runs one operation at a time: the leaves tile the busy time
    assert s["train_s"] + s["exchange_s"] == pytest.approx(s["busy_s"],
                                                           rel=1e-6)
    assert not s["has_collectives"]
    assert metrics["collective_exposed_ms_per_step"].read(
        {"summary": s}) is None
    ctx = {"summary": s, "required_flops": 1e12,
           "peak": {"bf16_flops": 197e12}}
    idle = 100 * (1 - grid.sum() / (t1 - t0))
    assert metrics["idle_share"].read(ctx) == pytest.approx(idle, rel=1e-5)
    assert metrics["train_ms_per_step"].read(ctx) == pytest.approx(
        train * 1e-6 / 2)
    assert metrics["exchange_ms_per_step"].read(ctx) == pytest.approx(
        other * 1e-6 / 2)
    assert metrics["mfu"].read(ctx) == pytest.approx(
        100 * 1e12 / ((t1 - t0) * 1e-9) / 197e12)
    top = s["top_ops"]
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def _op(chip, name, start, end, **kw):
    return dict({"chip": chip, "name": name, "scope": "", "start": start,
                 "dur": end - start}, **kw)


def test_collectives_by_hand():
    op = _op
    events = [op(0, "fusion.1", 0, 10), op(0, "all-reduce.2", 5, 20),
              op(0, "fusion.3", 18, 25),
              op(1, "all-gather-start.1", 0, 4), op(1, "fusion.1", 2, 6),
              op(1, "fusion.2", 10, 25)]
    s = tr.summarize(events, steps=1)
    # chip 0: the all-reduce runs alone over [10, 18]; chip 1: over [0, 2]
    assert s["collective_exposed_s"] == pytest.approx((8 + 2) / 2 * 1e-9)
    assert metrics["collective_exposed_ms_per_step"].read(
        {"summary": s}) == pytest.approx(5e-6)
    # busy: chip 0 [0, 25] = 25, chip 1 [0, 6] + [10, 25] = 21
    assert s["busy_s"] == pytest.approx(23e-9)
    assert s["window_s"] == pytest.approx(25e-9)
    # the longest gap on chip 0 is none; chip 0 is always busy
    assert s["idle_gaps"] == []
    # a collective in flight counts where no operation runs, never as busy:
    # chip 1's all-gather spans [3, 12], of which [6, 10] is bare
    events.append(dict(op(1, "all-gather-start.2", 3, 12), **{"async": True}))
    s2 = tr.summarize(events, steps=1)
    assert s2["busy_s"] == pytest.approx(s["busy_s"])
    assert s2["collective_exposed_s"] == pytest.approx((8 + 2 + 4) / 2 * 1e-9)
    assert [n for n, _ in s2["top_ops"]] == [n for n, _ in s["top_ops"]]


def test_collective_inside_a_loop_by_hand():
    """A loop spans its body: an all-gather started, in flight and awaited
    inside it, with a bare stretch, is exposed; the loop itself is no
    operation that hides it, and its gaps are idle."""
    events = [_op(0, "while.1", 0, 30),
              _op(0, "fusion.1", 1, 8, scope="jit(f)/while/body/vmap(jvp())"),
              _op(0, "all-gather-start.1", 8, 9),
              _op(0, "fusion.2", 10, 14),
              _op(0, "all-gather-done.1", 20, 24),
              _op(0, "fusion.3", 25, 29),
              _op(0, "all-gather-start.1", 9, 20, **{"async": True})]
    s = tr.summarize(events, steps=2)
    # collectives over [8, 24]; other leaves over [1, 8], [10, 14], [25, 29]
    assert s["collective_exposed_s"] == pytest.approx((2 + 10) * 1e-9)
    assert metrics["collective_exposed_ms_per_step"].read(
        {"summary": s}) == pytest.approx(6e-6)
    # busy: the leaves [1, 9], [10, 14], [20, 24], [25, 29]
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["window_s"] == pytest.approx(30e-9)
    assert s["train_s"] == pytest.approx(7e-9)
    assert s["exchange_s"] == pytest.approx(13e-9)
    assert [n for n, _ in s["top_ops"]] == [
        "fusion.1", "fusion.2", "all-gather-done.1", "fusion.3",
        "all-gather-start.1"]
    assert [g for _, g in s["idle_gaps"]] == pytest.approx(
        [6e-9, 1e-9, 1e-9])


def test_nothing_to_read():
    assert tr.summarize([], steps=4) == {}
    for m in metrics.values():
        assert m.read({"summary": {}, "required_flops": 1.0}) is None


def test_hlo_scopes_and_names():
    text = """
  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/vmap(transpose(jvp()))/mul" source_file="x.py"}
  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.12), metadata={op_name="jit(f)/copy"}
  %p = f32[8]{0} parameter(0)
"""
    scopes = tr.hlo_scopes(text)
    assert scopes == {"fusion.12": "jit(f)/vmap(transpose(jvp()))/mul",
                      "copy.3": "jit(f)/copy"}
    assert tr.instruction("%fusion.12 = f32[8]{0} fusion(...)") == \
        "fusion.12"
    assert tr.is_train(scopes["fusion.12"]) and not tr.is_train(
        scopes["copy.3"])
