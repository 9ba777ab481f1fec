"""The freshness ring push against a serial NumPy ring.

``push_and_update`` places every receipt of a step at once, by its rank
among the same step's receipts to its device. The oracle here pushes the
mules one at a time in mule order, as a ring buffer does, and hands its
ring to ``update_threshold`` (the threshold's float32 arithmetic, which
the compiler may contract, is the program's own); the two must agree bit
for bit in ``ages``, ``count`` and ``threshold``. The cases cover the commuter
benchmark's own traffic at M=2048 (most steps bring a space more than K
receipts, so most receipts are overwritten within their own step) and the
edges of the rank rule one at a time.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.freshness import (INF, FreshnessConfig, init_freshness,
                                  push_and_update, update_threshold)

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the commuter traffic of the benchmark's ``lstm-mlmule-commuter`` cell
COMMUTER = {"n_places": 8, "period": 192, "work_frac": 0.45, "commute": 6,
            "jitter": 8, "exchange_steps": 3, "duty_period": 0}
CFG = FreshnessConfig(alpha=0.1, beta=1.0, history=16, warmup=4,
                      init_threshold=1e6)
K = CFG.history


def _serial_push(state, fid, ages, deliver, cfg):
    """What the program did before: one mule at a time, a ring slot each,
    then the threshold from the ring."""
    ring = state["ages"].copy()
    count = state["count"].copy()
    for m in range(fid.shape[0]):
        if deliver[m] and fid[m] >= 0:
            f = fid[m]
            ring[f, count[f] % cfg.history] = np.float32(ages[m])
            count[f] += 1
    thr = np.asarray(_threshold(jnp.asarray(state["threshold"]),
                                jnp.asarray(ring), cfg))
    return {"ages": ring, "count": count, "threshold": thr}


def _schedule():
    spec = importlib.util.spec_from_file_location(
        "bench_schedule", ROOT / "bench" / "schedule.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _state(n_fixed, count=None, seed=0):
    """The initial rings, or rings pushed ``count`` times already."""
    state = {k: np.asarray(v) for k, v in init_freshness(n_fixed, CFG).items()}
    if count is not None:
        rng = np.random.default_rng(seed)
        count = np.asarray(count, np.int32)
        filled = np.arange(K)[None, :] < count[:, None]
        state["ages"] = np.where(
            filled, rng.uniform(0, 50, (n_fixed, K)), INF).astype(np.float32)
        state["count"] = count
        state["threshold"] = rng.uniform(5, 60, n_fixed).astype(np.float32)
    return state


def _ages(rng, m):
    return rng.uniform(0, 300, m).astype(np.float32)


def case_commuter():
    """200 steps of the benchmark's commuter rows at M=2048 (seed 12345)."""
    sched = _schedule()
    draws = sched.commuter_draws(12345, 2048, COMMUTER)
    rows = sched.commuter_rows(draws, COMMUTER, 0, 200)
    rng = np.random.default_rng(1)
    steps = [(rows["fixed_id"][i], _ages(rng, 2048),
              rows["exchange"][i] & rows["active"][i]) for i in range(200)]
    per_space = [np.bincount(f[d & (f >= 0)], minlength=8) for f, _, d in steps]
    # the cell's regime: most steps bring some space more than K receipts
    assert sum(int(c.max() > K) for c in per_space) > 100
    return _state(8), steps


def case_one_space_over_2k():
    """One space gets 2K + 5 receipts in one step, another gets three."""
    rng = np.random.default_rng(2)
    fid = np.full(48, 3, np.int32)
    fid[[4, 20, 40]] = 6
    fid[[0, 30, 47]] = -1
    steps = [(fid, _ages(rng, 48), np.ones(48, bool))]
    return _state(8, count=[0, 0, 0, 7, 0, 0, 2, 0]), steps


def case_no_receipts():
    """A step with no receipts after one with some: the rings and counts
    stay, the thresholds still move toward the rings' statistic."""
    rng = np.random.default_rng(3)
    fid = rng.integers(-1, 8, 64).astype(np.int32)
    first = (fid, _ages(rng, 64), rng.random(64) < 0.5)
    none = (np.where(rng.random(64) < 0.5, -1, fid).astype(np.int32),
            _ages(rng, 64), np.zeros(64, bool))
    none[2][none[0] < 0] = True            # delivering, but to no space
    return _state(8), [first, none]


def case_nothing_delivered():
    """From empty rings: every ``fid`` negative, then ``deliver`` all
    false; nothing is pushed and no threshold moves."""
    rng = np.random.default_rng(4)
    neg = (np.full(32, -1, np.int32), _ages(rng, 32), np.ones(32, bool))
    off = (rng.integers(0, 8, 32).astype(np.int32), _ages(rng, 32),
           np.zeros(32, bool))
    return _state(8), [neg, off]


def case_wrap_from_odd_count():
    """Rings pushed 5, 21, 35, ... times already wrap past slot K - 1."""
    rng = np.random.default_rng(5)
    count = [5, 21, 35, 13, 47, 1, 30, 17]
    fid = rng.integers(0, 8, 160).astype(np.int32)
    steps = [(fid, _ages(rng, 160), rng.random(160) < p) for p in (0.1, 0.9)]
    return _state(8, count=count, seed=5), steps


def case_interleaved():
    """Receipts to several spaces interleaved in mule order, around K a
    space: some spaces keep all of theirs, some lose the early ones."""
    rng = np.random.default_rng(6)
    steps = []
    for _ in range(6):
        fid = np.tile(np.arange(5, dtype=np.int32), 20)
        fid[rng.random(100) < 0.3] = rng.integers(0, 5)
        steps.append((fid, _ages(rng, 100), rng.random(100) < 0.8))
    return _state(5, count=[3, 0, 16, 9, 31], seed=6), steps


CASES = {"commuter_m2048": case_commuter,
         "one_space_over_2k": case_one_space_over_2k,
         "no_receipts": case_no_receipts,
         "nothing_delivered": case_nothing_delivered,
         "wrap_from_odd_count": case_wrap_from_odd_count,
         "interleaved": case_interleaved}

_push = jax.jit(push_and_update, static_argnums=4)
_threshold = jax.jit(update_threshold, static_argnums=2)


@pytest.mark.parametrize("case", list(CASES))
def test_push_matches_serial_ring_bitwise(case):
    want, steps = CASES[case]()
    got = {k: jnp.asarray(v) for k, v in want.items()}
    for i, (fid, ages, deliver) in enumerate(steps):
        want = _serial_push(want, fid, ages, deliver, CFG)
        got = _push(got, jnp.asarray(fid), jnp.asarray(ages),
                    jnp.asarray(deliver), CFG)
        for name in ("ages", "count", "threshold"):
            a, b = np.asarray(got[name]), want[name]
            assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
            assert np.array_equal(a.view(np.int32), b.view(np.int32)), \
                (case, i, name)
