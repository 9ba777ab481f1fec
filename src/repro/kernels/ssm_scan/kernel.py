"""Chunked Mamba2/SSD scan as a Pallas TPU kernel.

TPU-native adaptation (not a port of the CUDA selective-scan):
- grid = (batch*heads, n_chunks); the chunk axis is sequential on TPU, so the
  inter-chunk SSM state [P, N] lives in VMEM scratch and is carried across
  grid steps — the recurrence becomes a systolic sweep over chunks.
- within a chunk the quadratic SSD form runs on the MXU:
  (C B^T ⊙ decay) (dt·x) plus the state broadcast C·S, all fp32.
- B/C are shared across heads (Mamba2 multi-value layout); their BlockSpec
  index_map divides the bh index by the head count, so head replication never
  materializes in HBM.

Chunk size Q and head_dim P should be multiples of 8/128 for clean VMEM
tiling at full scale; interpret mode validates any size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int, nheads: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)            # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)          # [Q, 1]
    a = a_ref[0, 0, 0].astype(jnp.float32)      # scalar A_h (negative)
    bmat = b_ref[0].astype(jnp.float32)         # [Q, N]
    cmat = c_ref[0].astype(jnp.float32)         # [Q, N]

    loga = dt[:, 0] * a                         # [Q]
    cum = jnp.cumsum(loga)                      # [Q]
    dtx = dt * x                                # [Q, P]

    # intra-chunk: (C B^T ⊙ L) dtx, L_ij = exp(cum_i - cum_j) for j <= i
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    L = jnp.where(jj <= ii, decay, 0.0)
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    y_intra = jax.lax.dot_general(cb * L, dtx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: exp(cum_i) * C_i · S_prev
    state = state_scr[...]                      # [P, N]
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cmat, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [Q, P]

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- exp(cum_last) S + (dec ⊙ dtx)^T B
    dec_end = jnp.exp(cum[-1] - cum)            # [Q]
    sx = dtx * dec_end[:, None]                 # [Q, P]
    state_scr[...] = jnp.exp(cum[-1]) * state + jax.lax.dot_general(
        sx, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [P, N]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, Bmat, Cmat, *, chunk: int = 64,
                    init_state=None, interpret: bool):
    """x [B,S,H,P]; dt [B,S,H]; A [H]; Bmat/Cmat [B,S,N] -> (y, final_state).

    final_state is not returned by the kernel (scratch); callers needing the
    state for decode handoff use the chunked reference. init_state must be
    None (prefill-from-scratch), matching how the model uses the kernel.
    """
    assert init_state is None, "kernel path is prefill-from-scratch"
    b, s, h, p = x.shape
    n = Bmat.shape[-1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    sp = nc * chunk

    xr = jnp.moveaxis(x, 2, 1).reshape(b * h, sp, p)
    dtr = jnp.moveaxis(dt, 2, 1).reshape(b * h, sp, 1)
    ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h, 1, 1)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, nheads=h),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, cj: (bh, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, cj, h=h: (bh // h, cj, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, cj, h=h: (bh // h, cj, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh, cj: (bh, cj, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, ar, Bmat, Cmat)
    y = jnp.moveaxis(out.reshape(b, h, sp, p), 1, 2)[:, :s]
    # final state recomputed cheaply only when requested downstream; the
    # model's prefill path discards it (decode re-initializes from cache).
    return y, None
