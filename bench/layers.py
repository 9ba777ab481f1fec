"""A traced window split by the program's own layer scopes.

  python3 bench/layers.py --workload <cell> --seed <n> --seconds <s>

Makes one ``bench/run.py`` run of the cell with ``--trace 1`` and adds to
its result line ``layers``: the traced window by layer, in ms per population
step, under the names ``METRICS`` gives them. Needs the chip, as a run does.

The program names its layers with ``jax.named_scope`` (``LAYERS``); the
names reach the ``op_name`` of the compiled chunk program's HLO metadata,
which ``devtrace.load`` gives each operation as its ``scope``. ``split``
charges, on each chip, every instant of the window (``devtrace.summarize``'s:
the first operation to the end of the last on any chip) to the innermost
operation that runs or encloses it, loops and conditionals included, so the
gaps inside a loop are the loop's. The instant goes to the last layer scope
in that operation's ``op_name`` (the innermost one entered) or, where it has
none (an instruction the compiler added, with no metadata), to its enclosing
operation's layer. Instants with no operation, or none that names a layer,
are unscoped. Averaged over chips, ``sum(layers) + unscoped_s`` is the
window; ``layers`` holds the layers some operation names, and is empty for a
program without scopes.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

LAYERS = ("mule_expand", "mule_train", "mule_fresh", "mule_space",
          "mule_peer")
# the name of each layer's time per step; unscoped time has no layer
METRICS = {"local_train_ms_per_step": "mule_train",
           "fresh_ms_per_step": "mule_fresh",
           "space_ms_per_step": "mule_space",
           "peer_ms_per_step": "mule_peer",
           "expand_ms_per_step": "mule_expand",
           "unscoped_ms_per_step": None}


@functools.lru_cache(maxsize=None)
def _pattern(names: Tuple[str, ...]):
    return re.compile(r"(?<!\w)(" + "|".join(map(re.escape, names))
                      + r")(?!\w)")


def layer_of(scope: str, names: Tuple[str, ...] = LAYERS) -> Optional[str]:
    """The last of ``names`` in an ``op_name`` (the innermost scope
    entered), or None."""
    found = _pattern(tuple(names)).findall(scope)
    return found[-1] if found else None


def layer_times(ops: List[Dict], t0: float, t1: float,
                names: Tuple[str, ...] = LAYERS
                ) -> Dict[Optional[str], float]:
    """One chip's ``[t0, t1]`` by layer (None: unscoped), by a sweep over
    the nested operations: each instant goes to the innermost operation
    open at it. The parts add up to ``t1 - t0``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start"],
                                                   -ops[i]["dur"]))
    out: Dict[Optional[str], float] = {}
    stack: List[Tuple[Optional[str], float]] = []   # open: (layer, end)
    cur = t0

    def charge(layer, upto):
        nonlocal cur
        if upto > cur:
            out[layer] = out.get(layer, 0.0) + (upto - cur)
            cur = upto

    for i in order:
        e = ops[i]
        while stack and stack[-1][1] <= e["start"]:
            charge(*stack.pop())
        outer = stack[-1][0] if stack else None
        charge(outer, e["start"])
        own = layer_of(e["scope"], names)
        stack.append((outer if own is None else own, e["start"] + e["dur"]))
    while stack:
        charge(*stack.pop())
    charge(None, t1)
    return out


def split(events: List[Dict], names: Tuple[str, ...] = LAYERS) -> Dict:
    """``window_s``, ``layers`` (seconds by layer) and ``unscoped_s`` of
    ``devtrace.load``'s events, averaged over chips; empty without
    operations. ``names`` are the layer scopes: a model that names scopes
    of its own (``LAYERS + ("mule_ssm",)``) has their time taken out of
    the scopes they nest in."""
    ops = [e for e in events if e["chip"] >= 0 and not e.get("async")]
    if not ops:
        return {}
    chips = sorted({e["chip"] for e in ops})
    t0 = min(e["start"] for e in ops)
    t1 = max(e["start"] + e["dur"] for e in ops)
    layers = {k: 0.0 for k in {layer_of(e["scope"], names) for e in ops}
              - {None}}
    unscoped = 0.0
    for c in chips:
        for k, v in layer_times([e for e in ops if e["chip"] == c],
                                t0, t1, names).items():
            if k is None:
                unscoped += v
            else:
                layers[k] += v
    n = len(chips)
    return {"window_s": (t1 - t0) * 1e-9,
            "layers": {k: v * 1e-9 / n for k, v in sorted(layers.items())},
            "unscoped_s": unscoped * 1e-9 / n}


def per_step(s: Dict, steps: int) -> Dict[str, float]:
    """``split``'s result in ms per step under ``METRICS``' names: the
    layers the program names, and the unscoped rest where it names any."""
    if not s or not s["layers"] or steps <= 0:
        return {}
    return {name: 1e3 * (s["unscoped_s"] if layer is None
                         else s["layers"][layer]) / steps
            for name, layer in METRICS.items()
            if layer is None or layer in s["layers"]}


@contextlib.contextmanager
def keeping_events():
    """Within it, every ``devtrace.summarize`` call also appends its
    ``(events, steps)`` to the list it yields."""
    import devtrace
    kept: List[Tuple[List[Dict], int]] = []
    summarize = devtrace.summarize

    def keep(events, steps):
        kept.append((events, steps))
        return summarize(events, steps)

    devtrace.summarize = keep
    try:
        yield kept
    finally:
        devtrace.summarize = summarize


def main(argv=None) -> int:
    import run
    argv = list(sys.argv[1:] if argv is None else argv)
    with keeping_events() as kept:
        try:
            out = run.run(argv + ["--trace", "1"])
        except run.NoChip as e:
            print(f"bench/layers.py: {e}", file=sys.stderr)
            return 2
    events, steps = kept[-1] if kept else ([], 0)
    out["layers"] = per_step(split(events), steps)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
