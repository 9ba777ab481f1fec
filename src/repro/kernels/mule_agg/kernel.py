"""Fused population aggregation as a Pallas TPU kernel.

TPU-native design: the grid walks (D tile, mule tile) pairs. Each step
streams one [block_k, block_d] tile of the population and the matching
[F, block_k] slice of the assignment matrix from HBM, does one
[F, block_k] x [block_k, block_d] MXU matmul into an f32 accumulator that
stays in VMEM across the mule axis, and writes the [F, block_d] result at
the last mule tile — a single-pass, memory-bound reduce (arithmetic
intensity ~F MACs/element), which is exactly the roofline behaviour the
aggregation step should have. Tiling the reduction over mules keeps the
working set independent of M, so any population size fits VMEM.

Ragged edges are not padded in HBM (a padded copy of a [M, D] population
is a second population): the last D tile's out-of-range columns are
dropped by the output's masked store, and the last mule tile's
out-of-range rows are zeroed in the kernel before the matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (LANE, SUBLANE, VMEM_LIMIT_BYTES,
                                  fit_tiles, round_up)

BLOCK_K = 512                 # mule (reduction) tile before VMEM fitting


def tile_bytes(f: int, block_k: int, block_d: int, itemsize: int = 4) -> int:
    """Per-step VMEM working set: double-buffered A [F, block_k], W
    [block_k, block_d] and out [F, block_d] blocks, the f32 accumulator,
    and the f32 copy of the W tile the kernel computes on."""
    fp, kp, dp = round_up(f, SUBLANE), round_up(block_k, LANE), \
        round_up(block_d, LANE)
    return (2 * 4 * fp * kp + 2 * itemsize * block_k * dp
            + 2 * itemsize * fp * dp + 4 * fp * dp + 4 * block_k * dp)


def fit_blocks(f: int, m: int, d: int, block_d: int,
               itemsize: int = 4) -> tuple:
    """Legal (block_k, block_d), no larger than requested, whose working
    set fits the VMEM budget."""
    bd, bk = fit_tiles(f"mule_agg at F={f}, M={m}, D={d}", (d, m),
                       (block_d, BLOCK_K), (LANE, LANE),
                       lambda bd, bk: tile_bytes(f, bk, bd, itemsize))
    return bk, bd


def _agg_kernel(a_ref, w_ref, o_ref, acc_ref, *, m_total: int,
                block_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)          # [F, block_k]
    w = w_ref[...].astype(jnp.float32)          # [block_k, block_d]
    if m_total % block_k:                       # ragged last mule tile
        col = k * block_k + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        row = k * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_k, 1), 0)
        a = jnp.where(col < m_total, a, 0.0)
        w = jnp.where(row < m_total, w, 0.0)
    acc_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mule_agg_pallas(assign: jnp.ndarray, weights: jnp.ndarray, *,
                    block_d: int = 2048, interpret: bool) -> jnp.ndarray:
    """assign: [F, M]; weights: [M, D] -> [F, D].

    ``block_d`` is an upper bound: the tile used is the largest legal one
    below it whose working set fits the VMEM budget (``fit_blocks``).
    """
    f, m = assign.shape
    m2, d = weights.shape
    assert m == m2, (assign.shape, weights.shape)
    bk, bd = fit_blocks(f, m, d, block_d, weights.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_agg_kernel, m_total=m, block_k=bk),
        grid=(pl.cdiv(d, bd), pl.cdiv(m, bk)),
        in_specs=[
            pl.BlockSpec((f, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, bd), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((f, bd), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((f, d), weights.dtype),
        scratch_shapes=[pltpu.VMEM((f, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(assign, weights)
