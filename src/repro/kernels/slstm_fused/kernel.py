"""Fused sLSTM recurrence as a Pallas TPU kernel.

Why a kernel: the sLSTM recurrence is inherently sequential; lowered as a
lax.scan, every one of S steps re-streams the recurrent weights
r [4, H, P, P] from HBM (measured 99 TiB/device for xlstm-350m at 32k —
the worst roofline row in EXPERIMENTS.md). TPU-native fix: a sequential
grid over time with

- r resident in VMEM for the whole sweep (the BlockSpec index_map is
  constant, so Pallas never re-copies it between grid steps);
- the (h, c, n, m) cell state living in VMEM scratch across steps;
- per-step HBM traffic = one [B, 4, H, P] gate slice in + one [B, H, P]
  output slice out.

Per-step traffic drops from ~(|r| + states) to ~9·B·H·P·4 bytes — a
measured ~60x reduction of the memory-roofline term (§Perf pair 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(pre_ref, r_ref, h_out_ref, h_scr, c_scr, n_scr, m_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, -1e30)

    pre = pre_ref[0].astype(jnp.float32)         # [B, 4, H, P]
    r = r_ref[...].astype(jnp.float32)           # [4, H, P, P]
    h_prev = h_scr[...]                          # [B, H, P]

    def rec(g):
        # [B,H,P] x [H,P,P] -> [H,B,P] -> [B,H,P]
        out = jax.lax.dot_general(
            h_prev, r[g], (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)
        return jnp.moveaxis(out, 0, 1)

    z_pre = pre[:, 0] + rec(0)
    i_pre = pre[:, 1] + rec(1)
    f_pre = pre[:, 2] + rec(2)
    o_pre = pre[:, 3] + rec(3)
    logf = jax.nn.log_sigmoid(f_pre)
    m_prev = m_scr[...]
    m_new = jnp.maximum(logf + m_prev, i_pre)
    i_act = jnp.exp(i_pre - m_new)
    f_act = jnp.exp(logf + m_prev - m_new)
    c = f_act * c_scr[...] + i_act * jnp.tanh(z_pre)
    n = f_act * n_scr[...] + i_act
    h_new = jax.nn.sigmoid(o_pre) * c / jnp.maximum(n, 1e-6)

    h_scr[...] = h_new
    c_scr[...] = c
    n_scr[...] = n
    m_scr[...] = m_new
    h_out_ref[0] = h_new.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slstm_scan_pallas(pre, r, *, interpret: bool):
    """pre: [B, S, 4, H, P]; r: [4, H, P, P] -> h [B, S, H, P]."""
    b, s, four, h, p = pre.shape
    assert four == 4
    pre_t = jnp.moveaxis(pre, 1, 0)              # [S, B, 4, H, P]
    out = pl.pallas_call(
        _slstm_kernel,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, b, 4, h, p), lambda t: (t, 0, 0, 0, 0)),
            pl.BlockSpec((4, h, p, p), lambda t: (0, 0, 0, 0)),  # VMEM-resident
        ],
        out_specs=pl.BlockSpec((1, b, h, p), lambda t: (t, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, b, h, p), pre.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, h, p), jnp.float32),
            pltpu.VMEM((b, h, p), jnp.float32),
            pltpu.VMEM((b, h, p), jnp.float32),
            pltpu.VMEM((b, h, p), jnp.float32),
        ],
        interpret=interpret,
    )(pre_t, r)
    return jnp.moveaxis(out, 0, 1)
