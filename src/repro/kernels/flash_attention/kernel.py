"""Pallas TPU flash-attention kernel (blockwise online softmax).

TPU adaptation notes (vs the CUDA flash-attention the literature
targets): no warps/shared-memory — the unit of work is an MXU-shaped
VMEM tile.  The grid is (B, Hq, nq, nk) with the kv dimension innermost
and sequential ('arbitrary'); the (m, l, acc) running state lives in
2-D VMEM scratch across the nk iterations, q/k/v tiles are streamed
HBM->VMEM by BlockSpec.  Arrays are head-major inside the kernel, so a
tile is (rows, Dh) with Dh the lane dim; block sizes default to
MXU-aligned (128 multiples).

Semantics match ref.dense_attention / jnp_impl.blockwise_attention:
causal + optional sliding window + optional logit softcap + ragged
per-batch query positions (qpos input), GQA via head-index folding.
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


def _fa_kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, scale, window, softcap, S, bk, nk):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                # (bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, Dh)
    v = v_ref[0, 0]                                    # (bk, Dv)
    qpos = qpos_ref[0]                                 # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    kpos = jnp.where(kpos < S, kpos, -1)
    mask = (kpos <= qpos) & (kpos >= 0) & (qpos >= 0)
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # masked entries contribute exactly 0 — also in rows with no valid
    # key yet, where s - m_new would be 0 and exp() 1
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_new = acc_prev * corr + pv

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == nk - 1)
    def _fin():
        out = jnp.where(l_new > 0, acc_new / jnp.maximum(l_new, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, qpos, window: Optional[int] = None,
                           softcap: float = 0.0,
                           scale: Optional[float] = None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = False):
    """q (B,T,Hq,Dh); k (B,S,Hkv,Dh); v (B,S,Hkv,Dv); qpos (B,T) int32.
    `window` must be a static int or None (traced windows take the
    jnp blockwise path instead).  Returns (B,T,Hq,Dv).

    The kernel works head-major: q/k/v are laid out (B, H, T, D) so
    every block's last two dims are (rows, D) — the TPU's (sublane,
    lane) tile — and query positions ride as a (B, T, 1) column."""
    B, T, Hq, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    bq, bk = min(block_q, T), min(block_kv, S)
    nq, nk = pl.cdiv(T, bq), pl.cdiv(S, bk)
    Tp, Sp = nq * bq, nk * bk

    def heads_major(x, pad):
        x = jnp.swapaxes(x, 1, 2)
        return jnp.pad(x, [(0, 0), (0, 0), (0, pad), (0, 0)]) if pad else x

    qp = heads_major(q, Tp - T)
    kp = heads_major(k, Sp - S)
    vp = heads_major(v, Sp - S)
    qposp = jnp.pad(qpos, [(0, 0), (0, Tp - T)], constant_values=-1)[..., None]

    kernel = functools.partial(_fa_kernel, scale=scale, window=window,
                               softcap=softcap, S=S, bk=bk, nk=nk)
    grid = (B, Hq, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qposp, qp, kp, vp)
    return jnp.swapaxes(out[:, :, :T], 1, 2)
