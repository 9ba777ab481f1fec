"""A configuration the harness does not know by name, brought as files
alone (``stub/``): a tiny next-token model with int32 token ids in,
[B, T, V] logits, a loss at every position and its own FLOP counts in its
reference file, under a 16-mule ML Mule traffic file and its own limits.
On the CPU a whole run (inputs, weights, the program, the replay, the
reference and the judgement) comes out correct, and comes out not correct
with half of each batch left out; the bfloat16 control keeps the token ids
as they are; ``work.py`` takes the FLOPs from the reference file. Limits
(CPU, seeds 2147483689 and 11): the program reads at most 1e-7; the
bfloat16 control 6.7e-5 (change gap) and 4.3e-3 (diff), half the batch
6.8e-3 and 2.7e-2."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import program
import schedule
import work
from reference import Population, xent
from spec import Cell
from test_run_faults import _half_batch_sgd, _run

STUB = os.path.join(os.path.dirname(os.path.abspath(__file__)), "stub")


def _json(name):
    with open(os.path.join(STUB, name)) as f:
        return json.load(f)


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.syspath_prepend(STUB)       # the program side's module
    return Cell(name="stub-token-commuter", chips=1,
                config=_json("stub-token.json"),
                traffic=_json("mlmule-commuter-m16.json"),
                limits=_json("limits.json"), per_layer=[])


def test_sound_run_is_correct(cell, monkeypatch, tmp_path):
    out = _run(cell, monkeypatch, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_half_batch_is_not_correct(cell, monkeypatch, tmp_path):
    out = _run(cell, monkeypatch, tmp_path, _half_batch_sgd(cell))
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_control_keeps_token_ids(cell):
    ref = cell.reference
    inputs = program.make_inputs(cell, ref, 7)
    x = inputs.context["x"]
    assert x.dtype == jnp.int32 and x.shape[1:] == (cell.config["seq"],)
    draws = schedule.commuter_draws(inputs.seeds["schedule"],
                                    cell.traffic["mules"],
                                    cell.traffic["mobility"])
    pop = Population(cell, ref, inputs.context, inputs.key, draws,
                     dtype=jnp.bfloat16)
    assert pop.ctx["x"].dtype == jnp.int32
    assert np.array_equal(np.asarray(pop.ctx["x"]), np.asarray(x))


def test_flops_from_reference_file(cell):
    cfg, ref = cell.config, cell.reference
    per_example = 3 * 2 * 8 * (16 * 32 + 32 * 64)
    assert work.train_flops(cfg, ref) == per_example
    assert work.step_train_flops(cfg, 5, ref) == 5 * 4 * per_example
    with pytest.raises(ValueError, match="train_flops"):
        work.step_train_flops(cfg, 5)


@pytest.mark.parametrize("logits,y", [((4, 8, 64), (4, 8)),
                                      ((4, 64), (4, 1)),
                                      ((4, 64), (3,))])
def test_xent_refuses_other_shapes(logits, y):
    with pytest.raises(ValueError, match="loss"):
        xent(jnp.zeros(logits), jnp.zeros(y, jnp.int32))
