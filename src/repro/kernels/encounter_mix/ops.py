"""Dispatching wrapper for the fused peer-encounter mix.

``backend``:
- ``"ref"``       — the jnp oracle (engine default; exact, CPU-friendly).
- ``"pallas"``    — the tiled kernel, compiled on a TPU and interpreted
                    elsewhere, unless the caller passes ``interpret``.
- ``"interpret"`` — the tiled kernel, interpreter forced.
- ``"auto"``      — pallas on TPU, ref elsewhere.

Tile sizes: ``block_m``/``block_d`` left as ``None`` consult the autotune
cache (``repro.launch.autotune.tuned_encounter_blocks`` — the selection
committed in ``benchmarks/BENCH_roofline.json``, nearest tuned [M, D]
shape) and fall back to the pre-tuning hand defaults (256, 2048) without
one. Explicit values win; either way the kernel legalizes the tiles and
fits them to VMEM. ``REPRO_TUNE_CACHE`` repoints (or, empty, disables)
the cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.encounter_mix.kernel import (encounter_hop_pallas,
                                                encounter_mix_pallas)
from repro.kernels.encounter_mix.ref import (  # noqa: F401
    encounter_block, encounter_gate, encounter_mix_reference, normalize_mix)


def _resolve(backend: str, interpret: Optional[bool]):
    """Shared backend/interpret resolution for the two dispatchers."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend not in ("ref", "pallas", "interpret"):
        raise ValueError(f"unknown encounter_mix backend {backend!r}; "
                         "expected ref | pallas | interpret | auto")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if backend == "interpret":
        backend, interpret = "pallas", True
    return backend, interpret


def encounter_mix(pos: jnp.ndarray, area: jnp.ndarray,
                  active: Optional[jnp.ndarray], weights: jnp.ndarray, *,
                  radius: float = 0.15, backend: str = "ref",
                  block_m: int | None = None, block_d: int | None = None,
                  interpret: bool | None = None):
    """pos [M, 2] x area [M] x weights [M, D] -> (mix [M, D], mass [M])."""
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        return encounter_mix_reference(pos, area, active, weights,
                                       radius=radius)
    if active is None:
        active = jnp.ones((weights.shape[0],), bool)
    if block_m is None or block_d is None:
        from repro.launch.autotune import tuned_encounter_blocks
        tm, td = tuned_encounter_blocks(*weights.shape)
        block_m = tm if block_m is None else block_m
        block_d = td if block_d is None else block_d
    return encounter_mix_pallas(pos, area, active, weights, radius=radius,
                                block_m=block_m, block_d=block_d,
                                interpret=interpret)


def encounter_block_hop(pos_r, area_r, act_r, row0, pos_v, area_v, act_v,
                        col0, weights_v, radius: float = 0.15, *,
                        backend: str = "ref",
                        block_m: int | None = None,
                        block_d: int | None = None,
                        interpret: bool | None = None):
    """One ring hop's block partials — the ``encounter_block`` contract
    ((acc [R, D], mass [R]), unnormalized), backend-dispatched.

    ``"ref"`` *is* ``encounter_block`` (the ring stays bitwise-identical
    to its pre-dispatch form); ``"pallas"``/``"interpret"``/``"auto"``
    route through the tiled per-hop kernel with the same tuned-block
    lookup as ``encounter_mix``.
    """
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        return encounter_block(pos_r, area_r, act_r, row0,
                               pos_v, area_v, act_v, col0,
                               weights_v, radius)
    if act_r is None:
        act_r = jnp.ones((pos_r.shape[0],), bool)
    if act_v is None:
        act_v = jnp.ones((pos_v.shape[0],), bool)
    if block_m is None or block_d is None:
        from repro.launch.autotune import tuned_encounter_blocks
        tm, td = tuned_encounter_blocks(*weights_v.shape)
        block_m = tm if block_m is None else block_m
        block_d = td if block_d is None else block_d
    return encounter_hop_pallas(pos_r, area_r, act_r, row0,
                                pos_v, area_v, act_v, col0, weights_v,
                                radius=radius, block_m=block_m,
                                block_d=block_d, interpret=interpret)
