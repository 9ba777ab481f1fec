"""Fused peer-encounter mix as a tiled Pallas TPU kernel.

The retired dense path built the full [M, M] encounter matrix and ran a
``masked_group_mean`` over every model leaf — one [M, M] normalization pass
plus one skinny matmul *per leaf*, O(M^2 * L) memory traffic on top of the
O(M^2 * D) MACs. Here the [M, M] matrix never exists: the grid walks
``(row block, d block, column block)`` tiles. Each step recomputes the
distance/area/activity/self test for one [block_m, block_k] strip in
registers from two small geometry strips — the rows' [block_m, 5] and the
visiting columns' [5, block_k] (x, y, area, active, global row id) — and
a [block_m, block_k] x [block_k, block_d] MXU matmul accumulates the
neighbor sums in an f32 VMEM accumulator across the column blocks, next to
the per-row neighbor count (``mass``). At the last column block the tile
is written, normalized for the mix or as raw partials for a ring hop.

Tiling the contraction over columns keeps the working set independent of
M. Ragged edges are not padded in HBM: out-of-range rows and columns of
the output are dropped by masked stores, and the last column block's
out-of-range peers are masked out of the strip and their weight rows
zeroed before the matmul.

Arithmetic intensity per weight element is ~block_m MACs — the same
streaming roofline shape as ``mule_agg`` — while the dense path's
per-leaf [M, M] reads disappear entirely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (LANE, SUBLANE, VMEM_LIMIT_BYTES,
                                  fit_tiles, round_up)

BLOCK_K = 512                 # column (contraction) tile before VMEM fitting


def tile_bytes(block_m: int, block_k: int, block_d: int,
               itemsize: int = 4) -> int:
    """Per-step VMEM working set: double-buffered geometry strips (lane and
    sublane padded), W, out and mass blocks; the f32 accumulators; the f32
    W copy and the [block_m, block_k] strip temporaries."""
    mp, kp, dp = (round_up(block_m, SUBLANE), round_up(block_k, LANE),
                  round_up(block_d, LANE))
    geom = 2 * 4 * (mp * LANE + SUBLANE * kp)
    blocks = 2 * itemsize * (block_k * dp + mp * dp) + 2 * 4 * mp * LANE
    scratch = 4 * (mp * dp + mp * LANE)
    temps = 4 * block_k * dp + 6 * 4 * mp * kp
    return geom + blocks + scratch + temps


def fit_blocks(r: int, v: int, d: int, block_m: int, block_d: int,
               itemsize: int = 4) -> tuple:
    """Legal (block_m, block_k, block_d), no larger than requested, whose
    working set fits the VMEM budget."""
    bd, bk, bm = fit_tiles(
        f"encounter_mix at M={r} rows x {v} peers, D={d}", (d, v, r),
        (block_d, BLOCK_K, block_m), (LANE, LANE, SUBLANE),
        lambda bd, bk, bm: tile_bytes(bm, bk, bd, itemsize))
    return bm, bk, bd


def _encounter_kernel(gr_ref, gv_ref, w_ref, o_ref, mass_ref, acc_ref,
                      macc_ref, *, radius: float, v_total: int, block_k: int,
                      normalize: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        macc_ref[...] = jnp.zeros_like(macc_ref)

    gr = gr_ref[...].astype(jnp.float32)        # [block_m, 5] rows
    gv = gv_ref[...].astype(jnp.float32)        # [5, block_k] visiting peers
    dx = gr[:, 0:1] - gv[0:1, :]                # [block_m, block_k]
    dy = gr[:, 1:2] - gv[1:2, :]
    enc = dx * dx + dy * dy <= radius * radius
    enc &= gr[:, 2:3] == gv[2:3, :]             # area isolation
    enc &= (gr[:, 3:4] > 0) & (gv[3:4, :] > 0)  # both active
    enc &= gr[:, 4:5] != gv[4:5, :]             # global-id self exclusion
    w = w_ref[...].astype(jnp.float32)          # [block_k, block_d]
    if v_total % block_k:                       # ragged last column block
        col = k * block_k + jax.lax.broadcasted_iota(jnp.int32, enc.shape, 1)
        row = k * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_k, 1), 0)
        enc &= col < v_total
        w = jnp.where(row < v_total, w, 0.0)
    e = enc.astype(jnp.float32)
    macc_ref[...] += jnp.sum(e, axis=1, keepdims=True)
    acc_ref[...] += jax.lax.dot_general(
        e, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        acc, mass = acc_ref[...], macc_ref[...]
        if normalize:
            acc = acc / jnp.maximum(mass, 1e-12)
        o_ref[...] = acc.astype(o_ref.dtype)
        mass_ref[...] = mass


def _geometry(pos, area, act, g0) -> jnp.ndarray:
    """[n, 5] strip: x, y, area, active, float32 global row id."""
    n = pos.shape[0]
    return jnp.stack([pos[:, 0].astype(jnp.float32),
                      pos[:, 1].astype(jnp.float32),
                      area.astype(jnp.float32),
                      act.astype(jnp.float32),
                      g0 + jnp.arange(n, dtype=jnp.float32)], axis=1)


def _encounter_call(geom_r, geom_v, weights_v, out_dtype, *, radius: float,
                    block_m: int, block_d: int, normalize: bool,
                    interpret: bool):
    r = geom_r.shape[0]
    v, d = weights_v.shape
    bm, bk, bd = fit_blocks(r, v, d, block_m, block_d,
                            weights_v.dtype.itemsize)
    out, mass = pl.pallas_call(
        functools.partial(_encounter_kernel, radius=radius, v_total=v,
                          block_k=bk, normalize=normalize),
        grid=(pl.cdiv(r, bm), pl.cdiv(d, bd), pl.cdiv(v, bk)),
        in_specs=[
            pl.BlockSpec((bm, 5), lambda i, j, k: (i, 0)),
            pl.BlockSpec((5, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, d), out_dtype),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(geom_r, geom_v.T, weights_v)
    return out, mass[:, 0]


@functools.partial(jax.jit, static_argnames=("radius", "block_m", "block_d",
                                             "interpret"))
def encounter_mix_pallas(pos: jnp.ndarray, area: jnp.ndarray,
                         active: jnp.ndarray, weights: jnp.ndarray, *,
                         radius: float = 0.15, block_m: int = 256,
                         block_d: int = 2048, interpret: bool):
    """pos [M, 2], area [M], active [M], weights [M, D] -> (mix [M, D],
    mass [M]) — the ``encounter_mix_reference`` contract, tiled.

    ``block_m``/``block_d`` are upper bounds: the tiles used are the
    largest legal ones below them that fit the VMEM budget.
    """
    geom = _geometry(pos, area, active, 0)
    return _encounter_call(geom, geom, weights, weights.dtype, radius=radius,
                           block_m=block_m, block_d=block_d, normalize=True,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("radius", "block_m", "block_d",
                                             "interpret"))
def encounter_hop_pallas(pos_r, area_r, act_r, row0, pos_v, area_v, act_v,
                         col0, weights_v, *, radius: float = 0.15,
                         block_m: int = 256, block_d: int = 2048,
                         interpret: bool):
    """One ring hop of the mix, tiled: local rows [R] vs a visiting block
    [V] whose global rows start at ``col0`` — the ``encounter_block``
    contract ((acc [R, D], mass [R]), *unnormalized* partials that the
    ring accumulates across hops and normalizes once at the end).

    The float32 global row ids (``row0``/``col0`` + lane) in the geometry
    strips make self-exclusion work across blocks. ``row0``/``col0`` are
    traced (the ring derives them from ``axis_index``), so one compiled
    kernel serves every hop.
    """
    return _encounter_call(_geometry(pos_r, area_r, act_r, row0),
                           _geometry(pos_v, area_v, act_v, col0), weights_v,
                           jnp.float32, radius=radius, block_m=block_m,
                           block_d=block_d, normalize=False,
                           interpret=interpret)
