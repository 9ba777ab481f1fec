"""What decides ``correct``: the program's state after its first compiled
chunk against the reference's after the same steps, and exact checks on
the window's own result. Each number has a limit in
``bench/limits/<cell>.json``; PERF.md gives the readings each was set from.

Model numbers, by the worst leaf of the stacked population (every mule's
copy of a leaf together), against the weights before the first step, with
P the program's leaf, R the reference's and W the weights before:

- ``*_change_gap``: | |P - W| - |R - W| | / max(|R - W|, median leaf's
  |R - W|), the gap between the program's and the reference's norm of the
  change;
- ``*_diff``: |P - R| over the same denominator, the norm of their
  difference. A control in bfloat16 moves each leaf by about as much as
  the reference does, in another direction, so where the gap of norms
  cannot tell it from the program, this number does (PERF.md gives the
  readings of both).

A leaf whose reference change is under a thousandth of the median leaf's
is left out (its change is round-off, not training). ``mule_*`` reads the
mule models, ``fixed_*`` the spaces' models (ML Mule only; gossip leaves
them as they are).

Freshness (ML Mule): ``fresh_count`` and ``fresh_ages`` are the largest
differences in the spaces' receipt counts and age rings (exact: integers),
``fresh_threshold`` the largest relative difference in their thresholds.

Window: ``window_nonfinite`` counts values of the window's final models
that are not finite, and ``window_steps`` is how far the final step
counter lies from the steps the run drove (ML Mule only: gossip keeps no
step counter). ``window_fresh_count`` and ``window_fresh_ages`` (ML Mule)
are the largest differences in the spaces' receipt counts and age rings at
the window's end from the reference's over every step the run drove, which
follow from the schedule alone. All are exact, and so is
``window_compiles``, the compiles and traces inside the window.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _leaf_norms(p, r, w):
    """Per leaf: |P - W|, |R - W|, |P - R| (float32 sums of squares)."""
    def one(a, b, c):
        sq = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        return jnp.stack([sq(a - c), sq(b - c), sq(a - b)])
    return jax.tree.map(one, p, r, w)


def model_numbers(prefix: str, p, r, w) -> Dict[str, float]:
    rows = np.stack([np.asarray(x, np.float64)
                     for x in jax.tree.leaves(_leaf_norms(p, r, w))])
    d_p, d_r, diff = rows[:, 0], rows[:, 1], rows[:, 2]
    med = float(np.median(d_r))
    if med <= 0:
        return {}
    keep = d_r >= 1e-3 * med
    den = np.maximum(d_r, med)[keep]
    return {f"{prefix}_change_gap": float((np.abs(d_p - d_r)[keep] / den).max()),
            f"{prefix}_diff": float((diff[keep] / den).max())}


def fresh_numbers(p: Dict[str, Any], r: Dict[str, Any]) -> Dict[str, float]:
    """The distributed engine keeps an age histogram in place of the ring,
    so ``fresh_ages`` is read only where the program keeps the ring."""
    p = {k: np.asarray(v) for k, v in p.items()}
    thr = np.abs(p["threshold"].astype(np.float64) - r["threshold"])
    out = {"fresh_count": float(np.abs(p["count"] - r["count"]).max()),
           "fresh_threshold": float((thr / np.abs(r["threshold"])).max())}
    if "ages" in p:
        out["fresh_ages"] = float(np.abs(p["ages"].astype(np.float64)
                                         - r["ages"]).max())
    return out


def window_numbers(final: Dict[str, Any], method: str, steps_driven: int,
                   ref_fresh: Optional[Dict[str, Any]]) -> Dict[str, float]:
    """``window_steps`` and the freshness numbers only where the method
    keeps them in the state (ML Mule's space cycle does; gossip leaves
    ``t`` alone); ``window_fresh_ages`` only where the program keeps the
    ring."""
    bad = sum(int(jnp.sum(~jnp.isfinite(l)))
              for k in ("mule_models", "fixed_models")
              for l in jax.tree.leaves(final[k]))
    out = {"window_nonfinite": float(bad)}
    if method == "mlmule":
        out["window_steps"] = abs(float(final["t"]) - steps_driven)
        p = {k: np.asarray(v) for k, v in final["fresh"].items()}
        out["window_fresh_count"] = float(
            np.abs(p["count"] - ref_fresh["count"]).max())
        if "ages" in p:
            out["window_fresh_ages"] = float(np.abs(
                p["ages"].astype(np.float64) - ref_fresh["ages"]).max())
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> (bool, List[Dict[str, Any]]):
    """Every number must be at or under its limit; a number without a
    limit, or a limit whose number is missing, fails the run."""
    lines, ok = [], True
    for name in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(name), limits.get(name)
        good = v is not None and lim is not None and np.isfinite(v) \
            and v <= lim
        ok &= bool(good)
        lines.append({"name": name, "value": v, "limit": lim})
    return ok, lines


def describe(lines) -> List[str]:
    return [f"{l['name']} {l['value']!r} limit {l['limit']!r}" for l in lines]


def state_numbers(method: str, prog: Dict[str, Any], ref_mule, ref_fixed,
                  ref_fresh: Optional[Dict[str, Any]], w_mule, w_fixed
                  ) -> Dict[str, float]:
    """All model and freshness numbers of one comparison."""
    out = model_numbers("mule", prog["mule_models"], ref_mule, w_mule)
    if method == "mlmule":
        out.update(model_numbers("fixed", prog["fixed_models"], ref_fixed,
                                 w_fixed))
        if ref_fresh is not None:
            out.update(fresh_numbers(prog["fresh"], ref_fresh))
    return out
