"""The per-layer readers of the program's scopes (``bench/metrics/``), on
hand-made traces: each returns ``bench/layers.py``'s split of the events
``run.py`` hands it, and nothing where its scope is absent; a whole traced
run on the CPU, its trace replaced by a hand-made one, reports that split
under the metrics' names; and a model's own scope, passed to ``split``
beside the engine's, takes its instants out of the scope it nests in."""
import os

import pytest

import devtrace
import layers
import peaks
from spec import BENCH_DIR, load_module
from test_layers import _chunk_program, _op
from test_run_faults import _run, small_cell

READERS = ("local_train_ms_per_step", "fresh_ms_per_step",
           "space_ms_per_step", "peer_ms_per_step", "unscoped_ms_per_step")


def _read(name, events, steps):
    """A reader's value on the context ``run.py`` builds from events."""
    reader = load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))
    return reader.read({"events": events, "steps": steps,
                        "layers": layers.per_step(layers.split(events),
                                                  steps)})


def _two_chunks():
    return _chunk_program(0, 0) + _chunk_program(0, 108)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_split(name):
    events = _two_chunks()
    s = layers.split(events)
    layer = layers.METRICS[name]
    want = s["unscoped_s"] if layer is None else s["layers"][layer]
    assert _read(name, events, 2) == 1e3 * want / 2


@pytest.mark.parametrize("name,absent", [
    ("fresh_ms_per_step", True), ("peer_ms_per_step", True),
    ("space_ms_per_step", False), ("local_train_ms_per_step", False),
    ("unscoped_ms_per_step", False)])
def test_reader_without_its_scope(name, absent):
    """A program with training and a space exchange only: no freshness or
    peer time to read; and a program without scopes: nothing at all."""
    events = [_op(0, "fusion.1", 0, 10, "jit(f)/mule_train/dot_general"),
              _op(0, "fusion.2", 12, 20, "jit(f)/mule_space/dot_general")]
    assert (_read(name, events, 4) is None) == absent
    unscoped = [dict(e, scope="jit(f)/dot_general") for e in events]
    assert _read(name, unscoped, 4) is None


@pytest.mark.parametrize("name", ["lstm-mlmule-commuter",
                                  "cnn-gossip-commuter"])
def test_traced_run_reports_the_split(name, monkeypatch, tmp_path):
    """``run.py`` hands the readers the events it loads and their split
    over the window's steps; each of the cell's scope metrics is there."""
    events = _two_chunks()
    monkeypatch.setattr(devtrace, "load", lambda path, scopes: events)
    monkeypatch.setattr(peaks, "peak", lambda kind: {"bf16_flops": 1e12})
    cell = small_cell(name)
    out = _run(cell, monkeypatch, tmp_path, trace=1)
    assert out["correct"], out["checks"]
    want = layers.per_step(layers.split(events), out["attempted"])
    got = {m["name"]: out["metrics"][m["name"]]["value"]
           for m in cell.per_layer if m["name"] in layers.METRICS}
    assert set(got) >= {"local_train_ms_per_step", "unscoped_ms_per_step"}
    assert got == {k: want[k] for k in got}


def test_split_by_a_models_own_scope():
    """``mule_ssm`` inside training: with it among the names, its instants
    leave ``mule_train``; with the default names the split is today's."""
    events = _two_chunks()
    nested = [dict(e, scope=e["scope"].replace("mule_train/",
                                               "mule_train/mule_ssm/"))
              if e["name"] == "fusion.5" else e for e in events]
    default = layers.split(events)
    assert layers.split(events, names=layers.LAYERS) == default
    assert layers.split(nested) == default
    own = layers.split(nested, names=layers.LAYERS + ("mule_ssm",))
    assert own["layers"]["mule_ssm"] == pytest.approx(2 * 15e-9)
    assert own["layers"]["mule_train"] == pytest.approx(
        default["layers"]["mule_train"] - 2 * 15e-9)
    assert own["unscoped_s"] == default["unscoped_s"]
    for k in ("mule_expand", "mule_fresh", "mule_space", "mule_peer"):
        assert own["layers"][k] == default["layers"][k]
    assert sum(own["layers"].values()) == pytest.approx(
        sum(default["layers"].values()), rel=1e-12)
