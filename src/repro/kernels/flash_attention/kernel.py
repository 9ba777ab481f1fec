"""Blockwise GQA flash attention as a Pallas TPU kernel.

TPU-native design (not a CUDA port):
- grid = (batch*heads, n_q_blocks, n_k_blocks); the last grid axis is
  sequential on TPU, so the running-softmax state (m, l, acc) lives in VMEM
  scratch carried across k-block iterations.
- MXU-aligned blocks (block_q × head_dim and block_k × head_dim tiles,
  multiples of 128 recommended); fp32 accumulation, bf16 operands.
- causal + sliding-window handled by skipping whole k-blocks with ``pl.when``
  (a real compute skip on TPU, unlike a mask) and an in-block iota mask for
  the diagonal/band edges.
- GQA without materializing repeated KV heads: the k/v BlockSpec index_map
  divides the head index by the group size.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, block_q: int, block_k: int, seq_q: int, seq_k: int,
                 causal: bool, window: Optional[int]):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_off = seq_k - seq_q  # right-aligned query positions
    q_start = qi * block_q + q_off
    k_start = kj * block_k

    # whole-block band check on grid indices -> pl.when compute skip
    needed = None
    if causal:
        needed = k_start < q_start + block_q
    if window is not None:
        in_band = k_start + block_k > q_start - window
        needed = in_band if needed is None else jnp.logical_and(needed, in_band)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0].astype(jnp.float32)                  # [bk, d]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = kpos < seq_k
        if causal:
            valid &= kpos <= qpos
        if window is not None:
            valid &= kpos > qpos - window
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if needed is None:
        _compute()
    else:
        pl.when(needed)(_compute)

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "scale", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           scale: Optional[float] = None,
                           interpret: bool):
    """q: [B,S,H,D]; k,v: [B,Sk,KV,D] -> [B,S,H,D]."""
    b, s, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    g = h // n_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    nq = -(-s // block_q)
    nk = -(-sk // block_k)
    s_pad, sk_pad = nq * block_q, nk * block_k

    qr = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, sk, d)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, sk, d)
    if s_pad != s:
        qr = jnp.pad(qr, ((0, 0), (0, s_pad - s), (0, 0)))
    if sk_pad != sk:
        kr = jnp.pad(kr, ((0, 0), (0, sk_pad - sk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, sk_pad - sk), (0, 0)))

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=s, seq_k=sk, causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kj, g=g: (bh // g, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kj, g=g: (bh // g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out[:, :s].reshape(b, h, s, d)
    return jnp.moveaxis(out, 1, 2)
