"""The benchmark's copy of the commuter schedule equals the program's
generator bitwise, and counts the mules whose training a step keeps."""
import numpy as np
import pytest

import schedule

MOB = {"kind": "commuter", "n_places": 8, "period": 192, "work_frac": 0.45,
       "commute": 6, "jitter": 8, "exchange_steps": 3, "duty_period": 0,
       "duty_on_frac": 0.6}


def _program_rows(seed, m, mob, t0, n):
    from repro.mobility.streaming import commuter_stream
    gen = commuter_stream(seed, m, t0 + n,
                          **{k: v for k, v in mob.items() if k != "kind"})
    return gen, {k: np.asarray(v)
                 for k, v in gen.generate_chunk(None, t0, n).items()}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 5])
@pytest.mark.parametrize("t0,n", [(0, 200), (180, 40), (380, 30)])
@pytest.mark.parametrize("duty", [0, 24])
def test_mirror_equals_generator(seed, t0, n, duty):
    mob = dict(MOB, duty_period=duty, duty_on_frac=0.25)
    gen, got = _program_rows(seed, 64, mob, t0, n)
    draws = schedule.commuter_draws(seed, 64, mob)
    for k, v in gen.arrays().items():
        if k in draws:
            assert np.array_equal(np.asarray(v), draws[k]), k
    want = schedule.commuter_rows(draws, mob, t0, n)
    for k in ("fixed_id", "exchange", "active", "pos"):
        assert np.array_equal(got[k], want[k]), k
    assert np.array_equal(got["area"], want["area"])


def test_rows_cross_a_day_boundary():
    draws = schedule.commuter_draws(3, 64, MOB)
    whole = schedule.commuter_rows(draws, MOB, 150, 100)
    parts = [schedule.commuter_rows(draws, MOB, t, 25)
             for t in range(150, 250, 25)]
    for k in ("fixed_id", "exchange", "active"):
        assert np.array_equal(whole[k], np.concatenate([p[k] for p in parts]))
    # a home dwell that reaches midnight runs on into the morning: no
    # exchange restarts at t = 192 for mules home on both sides of it
    fid = whole["fixed_id"]
    home = draws["home"]
    both = (fid[41] == home) & (fid[42] == home)
    assert both.any()


def test_trained_per_step_by_hand():
    rows = {"fixed_id": np.array([[0, -1, 2, 3], [1, 1, -1, 0]]),
            "exchange": np.array([[1, 0, 1, 1], [1, 1, 0, 0]], bool),
            "active": np.array([[1, 1, 1, 0], [1, 1, 1, 1]], bool),
            "area": np.array([0, 0, 1, 1]),
            "pos": np.zeros((2, 4, 2), np.float32)}
    assert schedule.trained_per_step({"name": "mlmule"}, rows, 0).tolist() \
        == [2, 2]
    gossip = {"name": "gossip", "peer_every": 3, "radius": 0.15}
    # from t0 = 1 the second row is t = 2 (t % 3 == 2), all on: every
    # mule has a peer of its area
    assert schedule.trained_per_step(gossip, rows, 1).tolist() == [0, 4]
    # from t0 = 2 the first row trains; mule 3 is off, so mule 2 has no peer
    assert schedule.trained_per_step(gossip, rows, 2).tolist() == [2, 0]
    rows["active"][:] = True
    assert schedule.trained_per_step(gossip, rows, 2).tolist() == [4, 0]
