"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's root,
``bench/configs/<config>.json`` (via the entry's ``file``),
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json`` and the
per-layer readers ``bench/metrics/<metric>.py``. Nothing here imports JAX.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str = ""):
    """Import a Python file by path (bench file names may hold '-')."""
    name = name or "bench_" + hashlib.sha1(path.encode()).hexdigest()[:12]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # bench/configs/<config>.json
    traffic: Dict[str, Any]      # bench/traffic/<traffic>.json
    limits: Dict[str, Any]       # bench/limits/<cell>.json
    per_layer: List[Dict[str, Any]]  # the per-layer metrics it reports

    @property
    def reference(self):
        """The configuration's plain reference module, beside its file."""
        return load_module(os.path.join(ROOT, self.config["reference"]))


def load_cell(name: str) -> Cell:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                limits=_json(os.path.join(BENCH_DIR, "limits",
                                          name + ".json")),
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` 31-bit seeds derived from any whole number (JAX's PRNGKey and
    NumPy both take these), so a seed above 2**31 is as good as any."""
    import numpy as np
    ss = np.random.SeedSequence(abs(int(seed)))
    return [int(s) & 0x7FFFFFFF for s in ss.generate_state(n)]
