"""Multi-process bring-up: ``launch.multiprocess`` plumbing plus the
cluster-parity pin.

The cheap tests cover the pieces that must hold in any single process —
mesh validation that names both the requested shape and the device pool,
per-process jit-cache attribution, the placement helpers degrading to
plain device commits, and ``ordered_psum`` agreeing bitwise with
``lax.psum`` where the fold is trivial. The slow test is the actual
tentpole pin: a 2-process x 2-device ``jax.distributed`` CPU cluster
replaying the streamed engine (mid-run re-bucketing swaps included) must
produce final mule models bitwise identical to the same mesh shape in
one process, for both the paper method and the gossip baseline.
"""
import hashlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh, make_mule_mesh
from repro.launch.multiprocess import (ENV_COORDINATOR, ENV_NUM_PROCESSES,
                                       ENV_PROCESS_ID, host_replicated,
                                       initialize_from_env,
                                       local_cluster_env, pick_free_port,
                                       put_global, put_global_tree,
                                       spawn_local_cluster)

from conftest import run_with_devices


# ---------------------------------------------------------------------------
# cheap: single-process plumbing
# ---------------------------------------------------------------------------


def test_mule_mesh_validation_names_both_numbers():
    with pytest.raises(ValueError) as e:
        make_mule_mesh(4, 16)
    msg = str(e.value)
    assert "needs 64 devices" in msg
    assert f"jax.device_count()={jax.device_count()}" in msg
    assert "process(es)" in msg


def test_host_mesh_validation_names_both_numbers():
    with pytest.raises(ValueError) as e:
        make_host_mesh(data=8, model=8)
    assert "needs 64 devices" in str(e.value)
    assert f"jax.device_count()={jax.device_count()}" in str(e.value)


def test_jit_cache_stats_per_process_prefix():
    from repro.scenarios import jit_cache_stats
    plain = jit_cache_stats()
    pref = jit_cache_stats(per_process=True)
    prefix = f"p{jax.process_index()}/"
    assert set(pref) == {prefix + k for k in plain}
    for k, v in plain.items():
        assert pref[prefix + k] == v


def test_local_cluster_env_sets_the_triple():
    env = local_cluster_env(1, 3, "127.0.0.1:9999", 4, base_env={})
    assert env[ENV_COORDINATOR] == "127.0.0.1:9999"
    assert env[ENV_NUM_PROCESSES] == "3"
    assert env[ENV_PROCESS_ID] == "1"
    assert "xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    # an existing forced device count is left alone (the caller set it)
    env2 = local_cluster_env(
        0, 2, "c:1", 4,
        base_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "count=8" in env2["XLA_FLAGS"] and "count=4" not in env2["XLA_FLAGS"]


def test_initialize_from_env_is_noop_without_the_triple():
    assert initialize_from_env(env={}) is False


def test_pick_free_port_is_bindable():
    import socket
    port = pick_free_port()
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", port))


def test_put_global_single_process_roundtrip():
    from jax.sharding import PartitionSpec as P
    mesh = make_mule_mesh(1, 1)
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    sharded = put_global(x, mesh, P("data"))
    np.testing.assert_array_equal(np.asarray(sharded), x)
    replicated = put_global(x, mesh, P())
    np.testing.assert_array_equal(np.asarray(replicated), x)
    scalar = put_global(np.float32(3.5), mesh, P())
    assert float(scalar) == 3.5
    tree = put_global_tree({"a": x, "b": x[:, 0]}, mesh,
                           {"a": P("data"), "b": P()})
    np.testing.assert_array_equal(np.asarray(tree["a"]), x)
    np.testing.assert_array_equal(np.asarray(tree["b"]), x[:, 0])
    # fully-addressable arrays read straight back
    np.testing.assert_array_equal(host_replicated(replicated), x)


def test_ordered_psum_matches_psum_on_one_shard():
    """Where the rank-order fold is trivial (one shard) the deterministic
    reduction must be bitwise the raw ``lax.psum`` it replaced."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import ordered_pmean, ordered_psum

    mesh = make_mule_mesh(1, 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))

    def both(v):
        return (ordered_psum(v, "data"), jax.lax.psum(v, "data"),
                ordered_pmean(v, "data"), jax.lax.pmean(v, "data"))

    a, b, c, d = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=(P("data"),),
        out_specs=(P("data"),) * 4, check_vma=False))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(d))


# ---------------------------------------------------------------------------
# slow: the cluster-parity pin
# ---------------------------------------------------------------------------


_PARITY_CODE = """
import hashlib, os, sys
from repro.launch.multiprocess import initialize_from_env
initialize_from_env()
import jax, numpy as np
from jax.experimental import multihost_utils
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
from conftest import linear_population_setup
from repro.core.distributed import DistributedConfig, to_distributed_state
from repro.mobility import compact_colocation
from repro.scenarios import get_scenario, run_population_streamed

M, T = 8, 96
assert jax.device_count() == 4, jax.device_count()
mesh = jax.make_mesh((1, 4), ("pod", "data"))
for method in ("mlmule", "gossip"):
    pop, _, batch_fn, train_fn, pcfg = linear_population_setup(
        n_mules=M, n_steps=T)
    co = get_scenario("multi_area_migratory").colocation(0, M, T)
    dcfg = DistributedConfig(pop=pcfg, rebucket_every=16,
                             rebucket_threshold=0.1)
    st, aux = run_population_streamed(
        to_distributed_state(pop, dcfg), compact_colocation(co), batch_fn,
        train_fn, pcfg, jax.random.PRNGKey(7), n_steps=T, chunk_len=16,
        method=method, donate=False, mesh=mesh, dcfg=dcfg)
    w = multihost_utils.process_allgather(st["mule_models"]["w"],
                                          tiled=True)
    w = np.ascontiguousarray(np.asarray(w, np.float32))
    print("RESULT", method, aux["rebucket"]["swaps"],
          hashlib.sha256(w.tobytes()).hexdigest())
"""


def _parse_parity(stdout: str) -> dict:
    out = {}
    for line in stdout.splitlines():
        if line.startswith("RESULT "):
            _, method, swaps, digest = line.split()
            out[method] = (int(swaps), digest)
    assert set(out) == {"mlmule", "gossip"}, stdout
    return out


@pytest.mark.slow
def test_multiprocess_streamed_matches_single_process_bitwise():
    """2 processes x 2 devices == 1 process x 4 devices, bitwise, across
    re-bucketing swaps, for the paper method and the gossip baseline.

    Same (1, 4) mule mesh on both sides, so the shard_map program is
    identical — the pin is that crossing a process boundary (gloo
    collectives, per-process placement, the psum'd global argsort in the
    swap path) changes nothing: every rank's process-allgathered final
    weights hash to the single-process digest.
    """
    import os
    ref = _parse_parity(run_with_devices(_PARITY_CODE, n_devices=4))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    results = spawn_local_cluster(
        [sys.executable, "-c", _PARITY_CODE], num_processes=2,
        devices_per_process=2, base_env=env, timeout=600)
    for pid, res in enumerate(results):
        assert res.returncode == 0, \
            f"rank {pid} failed:\n{res.stdout}"
        got = _parse_parity(res.stdout)
        for method in ("mlmule", "gossip"):
            swaps_ref, digest_ref = ref[method]
            swaps, digest = got[method]
            assert swaps_ref >= 1, \
                f"{method}: drift never tripped a swap (weak workload)"
            assert swaps == swaps_ref, (method, pid, swaps, swaps_ref)
            assert digest == digest_ref, \
                f"{method}: rank {pid} diverged from single-process run"
