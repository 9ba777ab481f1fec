"""The split of a traced window by layer scope (``bench/layers.py``),
checked against an explicit containment computation, instant by instant:
hand traces with a scoped loop's gaps, a peer conditional holding training
and a gap between chunk programs; random nested traces on two chips; and
the recorded ``cnn-mlmule-commuter`` trace, whose program had no scopes."""
import json
import os
import re

import numpy as np
import pytest

import devtrace as tr
import layers
from spec import BENCH_DIR


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH_DIR, "data",
                           "trace-cnn-mlmule-commuter.json")) as f:
        return json.load(f)["events"]


def _op(chip, name, start, end, scope=""):
    return {"chip": chip, "name": name, "scope": scope, "start": start,
            "dur": end - start}


def test_recorded_trace_is_unscoped(recorded):
    """The program that made the recorded trace had no layer scopes: all
    of the window is unscoped, and no layer is reported."""
    s = layers.split(recorded)
    assert s["window_s"] == tr.summarize(recorded, steps=2)["window_s"]
    assert s["layers"] == {}
    assert s["unscoped_s"] == pytest.approx(s["window_s"], rel=1e-12)
    assert layers.per_step(s, 2) == {}


def _by_containment(ops, t0, t1):
    """One chip's window by layer, instant by instant: the operations that
    hold ``[t, t + 1)``, innermost first (shortest; of two with the same
    span the later listed), and the first of them that names a layer."""
    out = {}
    for t in range(int(t0), int(t1)):
        holding = [(e["dur"], -i, e) for i, e in enumerate(ops)
                   if e["start"] <= t and t + 1 <= e["start"] + e["dur"]]
        layer = None
        for _, _, e in sorted(holding, key=lambda h: h[:2]):
            named = [c for c in re.split(r"[/()]", e["scope"])
                     if c in layers.LAYERS]
            if named:
                layer = named[-1]
                break
        out[layer] = out.get(layer, 0) + 1
    return out


def _check_against_containment(events):
    s = layers.split(events)
    ops = [e for e in events if e["chip"] >= 0 and not e.get("async")]
    t0 = min(e["start"] for e in ops)
    t1 = max(e["start"] + e["dur"] for e in ops)
    chips = sorted({e["chip"] for e in ops})
    want = {}
    for c in chips:
        for k, v in _by_containment([e for e in ops if e["chip"] == c],
                                    t0, t1).items():
            want[k] = want.get(k, 0) + v
    n = len(chips)
    assert s["unscoped_s"] == pytest.approx(want.pop(None, 0) * 1e-9 / n)
    assert set(s["layers"]) >= set(want)
    for k, v in s["layers"].items():
        assert v == pytest.approx(want.get(k, 0) * 1e-9 / n), k
    # every instant of the window on every chip is charged once
    assert s["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert sum(s["layers"].values()) + s["unscoped_s"] == pytest.approx(
        s["window_s"], rel=1e-12)
    return s


BODY = "jit(chunk_replay)/while/body/closed_call/"


def _chunk_program(chip, t):
    """One chunk program from ``t``: the expand, then the scan's loop over
    a freshness push loop (with a conditional, a scatter and a copy the
    compiler added with no metadata), the space mix, and the peer
    conditional holding two training operations and its own mix."""
    def op(name, a, b, scope):
        return _op(chip, name, t + a, t + b, scope=scope)
    fresh = BODY + "mule_fresh/while"
    peer = BODY + "mule_peer/cond"
    return [
        op("fusion.0", 0, 3, "jit(chunk_replay)/mule_expand/add"),
        op("while.1", 4, 100, "jit(chunk_replay)/while"),
        op("fusion.1", 5, 8, BODY + "jit(_threefry_fold_in)/add"),
        op("while.2", 10, 40, fresh),
        op("fusion.2", 12, 15, fresh + "/body/closed_call/dynamic_slice"),
        op("conditional.1", 20, 28, fresh + "/body/closed_call/cond"),
        op("fusion.3", 21, 24,
           fresh + "/body/closed_call/cond/branch_1_fun/scatter-add"),
        op("copy-start.1", 30, 33, ""),
        op("fusion.4", 42, 48, BODY + "mule_space/dot_general"),
        op("conditional.2", 50, 90, peer),
        op("fusion.5", 55, 70,
           peer + "/branch_1_fun/mule_train/vmap(jvp())/dot_general"),
        op("fusion.6", 72, 80, peer + "/branch_1_fun/mule_train/"
           "vmap(transpose(jvp()))/dot_general"),
        op("fusion.7", 82, 86, peer + "/branch_1_fun/mule_peer/dot_general"),
        op("fusion.8", 92, 95, BODY + "select_n"),
    ]


def test_layers_by_hand():
    """Two chunk programs on one chip, with an unscoped gap between them
    in which the host dispatches the second."""
    events = (_chunk_program(0, 0) + _chunk_program(0, 108)
              + [{"chip": -1, "name": "mule/chunk", "start": 94,
                  "dur": 16}])
    s = _check_against_containment(events)
    assert s["window_s"] == pytest.approx(208e-9)
    # per chunk program: the push loop whole, gaps included (30); the peer
    # conditional less its training (40 - 15 - 8); the rest by its leaves
    per_chunk = {"mule_expand": 3, "mule_fresh": 30, "mule_space": 6,
                 "mule_peer": 17, "mule_train": 23}
    assert s["layers"] == pytest.approx(
        {k: 2 * v * 1e-9 for k, v in per_chunk.items()})
    # [3, 4], the loop's own gaps outside the scopes, [100, 108]
    assert s["unscoped_s"] == pytest.approx((208 - 2 * 79) * 1e-9)
    # the autodiff split of the trace reduction does not read the scopes
    assert tr.summarize(events, 2)["train_s"] == pytest.approx(2 * 23e-9)
    # the longest gap, from the first program's last leaf to the second's
    # first, falls in the host's span of the second chunk's dispatch
    assert tr.summarize(events, 2)["idle_gaps"][0] == [
        "mule/chunk", pytest.approx(13e-9)]
    got = layers.per_step(s, 2)
    assert set(got) == set(layers.METRICS)
    for name, layer in layers.METRICS.items():
        want = s["unscoped_s"] if layer is None else s["layers"][layer]
        assert got[name] == pytest.approx(1e3 * want / 2), name
    assert sum(got.values()) == pytest.approx(1e3 * s["window_s"] / 2)


def test_layers_two_chips_by_hand():
    """Chips that start and end apart: each is charged the whole window,
    its idle ends unscoped, and the layers are averaged over chips."""
    events = (_chunk_program(0, 0) + _chunk_program(0, 108)
              + _chunk_program(1, 6) + _chunk_program(1, 120))
    s = _check_against_containment(events)
    assert s["window_s"] == pytest.approx(220e-9)
    assert s["layers"]["mule_fresh"] == pytest.approx(60e-9)
    assert s["unscoped_s"] == pytest.approx(
        ((220 - 158) + (220 - 158)) / 2 * 1e-9)


def test_per_step_reports_only_named_layers():
    """A program without a peer exchange reports no peer time."""
    events = [_op(0, "fusion.1", 0, 10, "jit(f)/mule_train/dot_general"),
              _op(0, "fusion.2", 12, 20, "jit(f)/mule_space/dot_general")]
    got = layers.per_step(layers.split(events), 4)
    assert got == pytest.approx({"local_train_ms_per_step": 2.5e-6,
                                 "space_ms_per_step": 2e-6,
                                 "unscoped_ms_per_step": 0.5e-6})


@pytest.mark.parametrize("seed", range(6))
def test_layers_random_nesting(seed):
    """Random nested operations (loops in loops; scoped, unscoped and with
    no metadata) on two chips, against the containment computation."""
    rng = np.random.default_rng(seed)
    names = list(layers.LAYERS) + [None, None]

    def scope_of(parent):
        if rng.random() < 0.15:
            return ""                     # added by the compiler
        pick = names[rng.integers(len(names))]
        return parent + ("/" + pick if pick else "/op")

    def fill(chip, lo, hi, parent, depth, out):
        t = lo
        while t < hi - 2:
            a = t + int(rng.integers(0, 3))
            b = min(hi, a + 1 + int(rng.integers(1, 12)))
            if a >= b:
                break
            scope = scope_of(parent)
            out.append(_op(chip, f"op.{len(out)}", a, b, scope=scope))
            if depth < 3 and b - a > 3 and rng.random() < 0.5:
                fill(chip, a, b, scope + "/while/body", depth + 1, out)
            t = b
        return out

    events = []
    for chip in (0, 1):
        events = fill(chip, int(rng.integers(0, 5)), 120, "jit(f)", 0,
                      events)
    _check_against_containment(events)


def test_keeping_events_sees_summarize_and_restores_it(recorded):
    """``main`` reads the events a run hands the trace reduction, and
    leaves the reduction as it found it."""
    before = tr.summarize
    with layers.keeping_events() as kept:
        s = tr.summarize(recorded, steps=2)
    assert tr.summarize is before
    assert kept == [(recorded, 2)]
    assert s == tr.summarize(recorded, steps=2)
