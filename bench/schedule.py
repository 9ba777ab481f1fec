"""The benchmark's own copy of the commuter schedule.

A traffic file's ``mobility`` block (kind ``commuter``) names the day a
mule lives: home until a per-(mule, day) jitter ``j``, a commute of
``commute`` steps, ``work_frac * period`` steps at work, a commute back,
home again. Places are ``n_places`` spaces in ``n_places // 4`` areas
(area = home // 4); a mule in a place exchanges with it every
``exchange_steps`` steps of its dwell, and a dwell at home that reaches
midnight runs on into the next morning. With ``duty_period`` set, mule
``m`` is switched on while ``(t + aphase[m]) % duty_period < duty_on``,
and mule ``t % M`` is always on.

The per-mule draws (home, work, jitter phase, odd day stride, duty phase)
come from ``jax.random`` on the host with the key discipline below, and the
schedule itself is NumPy integer arithmetic. ``run.py`` expands the
program's generator at set-up and refuses to run where it differs from
this copy, so the traffic cannot change with the program.

The copy serves two more ends: ``trained_per_step`` counts the mules whose
training a step keeps (``mfu``'s numerator), and the reference replays
from these rows rather than from anything the program generated.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def commuter_draws(seed: int, n_mules: int, mob: Dict) -> Dict[str, np.ndarray]:
    """Per-mule parameters: ``split(PRNGKey(seed), 5)`` gives the keys of
    home, work offset, phase, stride and duty phase, in that order."""
    import jax
    import jax.numpy as jnp
    with jax.default_device(jax.devices("cpu")[0]):
        kh, kw, kp, ks, ka = jax.random.split(jax.random.PRNGKey(seed), 5)
        n_places, jitter = mob["n_places"], mob["jitter"]
        home = jax.random.randint(kh, (n_mules,), 0, n_places, jnp.int32)
        off = jax.random.randint(kw, (n_mules,), 1, n_places, jnp.int32)
        out = {
            "home": np.asarray(home, np.int64),
            "work": (np.asarray(home, np.int64) + np.asarray(off)) % n_places,
            "phase": np.asarray(jax.random.randint(
                kp, (n_mules,), 0, jitter + 1, jnp.int32), np.int64),
            "stride": 2 * np.asarray(jax.random.randint(
                ks, (n_mules,), 0, 1 << 15, jnp.int32), np.int64) + 1,
        }
        if mob.get("duty_period", 0):
            out["aphase"] = np.asarray(jax.random.randint(
                ka, (n_mules,), 0, mob["duty_period"], jnp.int32), np.int64)
    return out


def work_len(mob: Dict) -> int:
    return max(int(mob["work_frac"] * mob["period"]), 1)


def duty_on(mob: Dict) -> int:
    dp = mob.get("duty_period", 0)
    return max(int(mob.get("duty_on_frac", 0.6) * dp), 1) if dp else 0


def commuter_rows(draws: Dict[str, np.ndarray], mob: Dict, t0: int,
                  n: int) -> Dict[str, np.ndarray]:
    """Steps ``t0 .. t0 + n``: ``fixed_id`` [n, M] (-1 while commuting),
    ``exchange`` [n, M], ``active`` [n, M], ``area`` [M], ``pos`` [n, M, 2]
    (all zero: every mule of an area is within reach of every other)."""
    p, c, jit = mob["period"], mob["commute"], mob["jitter"] + 1
    wl = work_len(mob)
    ts = np.arange(t0, t0 + n, dtype=np.int64)
    day, w = ts // p, (ts % p)[:, None]
    phase, stride = draws["phase"][None], draws["stride"][None]
    j = (phase + day[:, None] * stride) % jit
    w0 = j + c
    w1 = w0 + wl
    we = w1 + c
    morning, at_work, evening = w < j, (w >= w0) & (w < w1), w >= we
    fid = np.where(morning | evening, draws["home"][None],
                   np.where(at_work, draws["work"][None], -1))
    we_prev = (phase + (day[:, None] - 1) * stride) % jit + 2 * c + wl
    base = (day * p)[:, None]
    morning_start = np.where((day[:, None] > 0) & (we_prev < p),
                             base - p + we_prev, base)
    run_start = np.where(morning, morning_start,
                         np.where(at_work, base + w0, base + we))
    dwell = ts[:, None] - run_start + 1
    exch = (fid >= 0) & (dwell % mob["exchange_steps"] == 0)
    m = draws["home"].shape[0]
    if mob.get("duty_period", 0):
        act = ((ts[:, None] + draws["aphase"][None]) % mob["duty_period"]
               < duty_on(mob))
        act |= np.arange(m)[None] == (ts % m)[:, None]
    else:
        act = np.ones(fid.shape, bool)
    return {"fixed_id": fid.astype(np.int32), "exchange": exch,
            "active": act, "area": (draws["home"] // 4).astype(np.int32),
            "pos": np.zeros(fid.shape + (2,), np.float32)}


def peers(area: np.ndarray, active: np.ndarray, pos: np.ndarray,
          radius: float) -> np.ndarray:
    """[M] number of encounter peers: same area, within ``radius``, both
    active, not oneself."""
    d2 = ((pos[:, None] - pos[None]) ** 2).sum(-1)
    enc = (area[:, None] == area[None]) & (d2 <= radius ** 2)
    enc &= active[:, None] & active[None]
    np.fill_diagonal(enc, False)
    return enc.sum(1)


def trained_per_step(method: Dict, rows: Dict[str, np.ndarray],
                     t0: int) -> np.ndarray:
    """Mules whose trained model a step keeps, for each step of ``rows``.

    ``mlmule`` keeps the training of mules that deliver (in a space, on an
    exchange step, active); ``gossip`` that of active mules with a peer, on
    steps ``t % every == every - 1``. Training the program computes and
    then discards is not counted."""
    if method["name"] == "mlmule":
        return (rows["exchange"] & (rows["fixed_id"] >= 0)
                & rows["active"]).sum(1)
    if method["name"] == "gossip":
        every = method["peer_every"]
        out = np.zeros(rows["fixed_id"].shape[0], np.int64)
        for i in range(out.shape[0]):
            if (t0 + i) % every == every - 1:
                out[i] = (peers(rows["area"], rows["active"][i],
                                rows["pos"][i], method["radius"]) > 0).sum()
        return out
    raise ValueError(f"no trained-mule count for method {method['name']!r}")
