"""The control of every cell's comparison, at a size a test run holds: the
reference put in the program's place in bfloat16 (the nearest precision
below the configurations' float32), with half of each batch left out, and
on a mule mesh the program with the exchange between chips left out, must
each fail one of the cell's limits, while the program passes them.
The limits' own readings were taken on the chip at the cells' sizes with
``bench/control.py``; these runs are the CPU, at the published widths and
16 mules (at cut widths a bfloat16 model rounds too little to show)."""
import copy

import pytest

import compare
import control
from test_run_faults import PATHS, _load


def few_mules(name):
    cell = _load(name)
    cell.traffic = dict(copy.deepcopy(cell.traffic), mules=16)
    cell.traffic["data"] = dict(cell.traffic["data"], per_class=8)
    cell.traffic.pop("reference_block", None)
    return cell


def _model_limits(cell):
    """The limits of the numbers a comparison of states reads."""
    return {k: v for k, v in cell.limits.items()
            if not k.startswith("window_")}


@pytest.fixture(scope="module")
def readings():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    out = {}
    for name in PATHS:
        cell = few_mules(name)
        out[name] = (cell, dict(control.readings(cell, 2147483689, True)))
    return out


@pytest.mark.parametrize("name", PATHS)
def test_program_passes(readings, name):
    cell, r = readings[name]
    numbers = {k: v for k, v in r["program"].items()
               if not k.endswith(("_unmoved", "_leaf"))}
    ok, lines = compare.judge(numbers, _model_limits(cell))
    assert ok, lines


STAND_INS = [(name, kind) for name in PATHS
             for kind in ("control", "half_batch")
             + (("no_exchange",) if _load(name).traffic.get("mesh")
                else ())]


@pytest.mark.parametrize("name,kind", STAND_INS)
def test_stand_in_fails(readings, name, kind):
    cell, r = readings[name]
    ok, lines = compare.judge(r[kind], _model_limits(cell))
    assert not ok, lines
