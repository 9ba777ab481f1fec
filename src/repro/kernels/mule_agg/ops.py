"""Dispatching wrapper for the fused population aggregation.

``block_d`` tuning: the kernel streams [block_k, block_d] tiles; too small
pays grid overhead, too large overflows VMEM. ``block_d=None`` uses
``pick_block_d``, which consults the autotune cache — the selection
committed in ``benchmarks/BENCH_roofline.json`` by
``repro.launch.autotune`` (re-measure with
``python -m benchmarks.engine_micro --roofline``; the selection is the
argmin of a per-shape candidate sweep on the CPU interpret path, which
tracks relative block behaviour, not TPU latency) — and falls back to the
hand-measured constant below when no cache is available. Whatever the
request, the kernel itself legalizes the tile and fits it to VMEM.
``REPRO_TUNE_CACHE`` repoints (or, empty, disables) the cache.

``interpret`` follows the platform (compiled on a TPU, the Pallas
interpreter elsewhere) unless the caller passes it.
"""
from __future__ import annotations

import jax

from repro.kernels.mule_agg.kernel import mule_agg_pallas
from repro.kernels.mule_agg.ref import mule_agg_reference  # noqa: F401

# Pre-cache fallback, measured by the retired kernels_micro block_d sweep on
# the CPU interpret path: the sweep came out monotone at every D (2^12..2^18)
# — per-tile dispatch overhead dominates, so the largest tile always won.
_BLOCK_D_MEASURED = 4096


def pick_block_d(d: int) -> int:
    """Tuned D-tile size: the autotune cache's selection for the nearest
    measured shape, else the hand-measured fallback constant."""
    from repro.launch.autotune import tuned_block_d
    return tuned_block_d(d, default=_BLOCK_D_MEASURED)


def mule_agg(assign, weights, *, block_d: int | None = None,
             backend: str = "auto", interpret: bool | None = None):
    """assign [F, M] x weights [M, D] -> [F, D]."""
    if backend == "ref":
        return mule_agg_reference(assign, weights)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_d is None:
        block_d = pick_block_d(weights.shape[1])
    return mule_agg_pallas(assign, weights, block_d=block_d, interpret=interpret)
