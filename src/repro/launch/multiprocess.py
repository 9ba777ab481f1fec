"""Multi-process bring-up for the mule mesh (``jax.distributed``).

Three small layers, in the order a run uses them:

1. **Spawn** — ``spawn_local_cluster`` launches N copies of an argv as a
   local CPU cluster (one coordinator port, ``N`` processes with
   ``devices_per_process`` forced host devices each).  The environment
   each child needs is built by ``local_cluster_env`` and must be in
   place *before the child imports jax* — which is why the cluster is
   spawned as subprocesses rather than forked workers.
2. **Init** — inside each process, ``initialize_from_env`` (or the
   explicit ``initialize_process``) selects the ``gloo`` CPU
   collectives backend and calls ``jax.distributed.initialize``.  After
   this, ``jax.devices()`` spans the whole cluster and every mesh built
   by ``launch.mesh.make_mule_mesh`` is a multi-host mesh.
3. **Place** — ``put_global`` / ``put_global_tree`` commit host arrays
   to a (possibly multi-host) ``NamedSharding``.  Leaves sharded on
   their leading axis go through
   ``jax.make_array_from_process_local_data`` so each process hands the
   runtime only its own row block (its shard of the generator columns
   and mule state); replicated leaves go through
   ``jax.make_array_from_callback``.

Everything degrades to a no-op single-process path: ``num_processes=1``
skips ``jax.distributed`` entirely and ``put_global`` on a
single-process mesh is an ordinary ``device_put``-equivalent, so the
engines call these helpers unconditionally.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

ENV_COORDINATOR = "REPRO_MP_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_MP_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_MP_PROCESS_ID"

_initialized = False


def pick_free_port(host: str = "127.0.0.1") -> int:
    """Bind-then-release a port for the coordinator of a local cluster."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def local_cluster_env(process_id: int, num_processes: int, coordinator: str,
                      devices_per_process: int,
                      base_env: Optional[Dict[str, str]] = None
                      ) -> Dict[str, str]:
    """Environment for one process of a local CPU cluster.

    Must be installed before the child imports jax: the forced host
    device count is read at backend bring-up and ``JAX_PLATFORMS=cpu``
    keeps the child off any accelerator the parent may see.  The
    coordinator/process-id triple rides on ``REPRO_MP_*`` variables that
    ``initialize_from_env`` consumes inside the child.
    """
    env = dict(os.environ if base_env is None else base_env)
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices_per_process}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(process_id)
    return env


def initialize_process(coordinator_address: str, num_processes: int,
                       process_id: int) -> None:
    """``jax.distributed`` bring-up over the gloo CPU collectives backend.

    Call before any jax computation (the distributed service must come
    up before the backend initializes).  Idempotent; a 1-process
    "cluster" skips ``jax.distributed`` entirely.
    """
    global _initialized
    if _initialized or num_processes <= 1:
        return
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True


def initialize_from_env(env=None) -> bool:
    """Init from ``REPRO_MP_*`` variables; returns True when they were set.

    The hook every spawned entry point calls first thing: parents launch
    children via ``spawn_local_cluster``/``local_cluster_env`` and the
    child picks the coordinator triple back up here.
    """
    env = os.environ if env is None else env
    coord = env.get(ENV_COORDINATOR)
    if not coord:
        return False
    initialize_process(coord, int(env[ENV_NUM_PROCESSES]),
                       int(env[ENV_PROCESS_ID]))
    return True


def spawn_local_cluster(argv: Sequence[str], num_processes: int,
                        devices_per_process: int = 1, *,
                        coordinator: Optional[str] = None,
                        base_env: Optional[Dict[str, str]] = None,
                        capture: bool = True, timeout: Optional[float] = None,
                        ) -> List[subprocess.CompletedProcess]:
    """Run ``argv`` as an N-process local CPU cluster; one result per rank.

    All ranks launch concurrently (they must — ``jax.distributed``
    blocks every process until the whole cluster has dialed the
    coordinator).  stdout/stderr are captured per rank when ``capture``;
    the caller decides which rank's output to surface.
    """
    coord = coordinator or f"127.0.0.1:{pick_free_port()}"
    pipes = dict(stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                 text=True) if capture else {}
    procs = [subprocess.Popen(
        list(argv),
        env=local_cluster_env(pid, num_processes, coord,
                              devices_per_process, base_env),
        **pipes) for pid in range(num_processes)]
    results = []
    try:
        for pid, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            results.append(subprocess.CompletedProcess(
                list(argv), p.returncode, stdout=out, stderr=None))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return results


# ---------------------------------------------------------------------------
# per-process data placement
# ---------------------------------------------------------------------------


def put_global(x, mesh, spec):
    """Commit ``x`` to ``NamedSharding(mesh, spec)``, multi-host safe.

    Arrays that are already global (not fully addressable — i.e. already
    placed on a multi-host mesh) pass through untouched.  In a single
    process this is ``jax.device_put`` (device to device, a no-op for an
    array already placed so).  Across processes, leaves whose leading
    axis is sharded hand jax only this process's contiguous row block via
    ``jax.make_array_from_process_local_data``; everything else
    (replicated leaves, scalars, keys) goes through
    ``jax.make_array_from_callback``, which only materializes addressable
    shards.
    """
    import jax
    from jax.sharding import NamedSharding

    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return x
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    row_sharded = (arr.ndim > 0 and len(spec) > 0 and spec[0] is not None)
    if row_sharded:
        idx_map = sharding.addressable_devices_indices_map(arr.shape)
        starts = [idx[0].start or 0 for idx in idx_map.values()]
        stops = [arr.shape[0] if idx[0].stop is None else idx[0].stop
                 for idx in idx_map.values()]
        local = np.ascontiguousarray(arr[min(starts):max(stops)])
        return jax.make_array_from_process_local_data(sharding, local,
                                                      arr.shape)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def put_global_tree(tree, mesh, specs):
    """``put_global`` over a pytree with a matching specs tree."""
    import jax
    return jax.tree.map(lambda x, s: put_global(x, mesh, s), tree, specs)


def gather_global(x) -> np.ndarray:
    """Host numpy copy of any array, multi-host safe.

    Replicated leaves read this process's replica (no traffic); leaves
    sharded across processes allgather their row blocks
    (``multihost_utils.process_allgather``). The hook experiment drivers
    use to pull a distributed run's final state back for host-side
    metrics — on single-process arrays it is exactly ``np.asarray``.
    """
    import jax
    if not (isinstance(x, jax.Array) and not x.is_fully_addressable):
        return np.asarray(x)
    shard = x.addressable_shards[0]
    if shard.data.shape == x.shape:
        return np.asarray(shard.data)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def host_replicated(x) -> np.ndarray:
    """Read a replicated global array back on the host, multi-host safe.

    ``np.asarray`` refuses arrays whose devices span processes; for a
    replicated value every process's first addressable shard *is* the
    full value, so read that.  Sharded arrays don't belong here —
    gather them (e.g. ``multihost_utils.process_allgather``) instead.
    """
    import jax
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        shard = x.addressable_shards[0]
        if shard.data.shape != x.shape:
            raise ValueError(
                f"host_replicated needs a replicated array; got shard shape "
                f"{shard.data.shape} for global shape {x.shape}")
        return np.asarray(shard.data)
    return np.asarray(x)
