"""Blocked MXU GEMM Pallas kernel — the per-device compute of the
paper's GEMM/2MM benchmarks (each HDArray device runs its work-region
rows; this kernel is what HDArrayApplyKernel would launch per shard on
TPU instead of an OpenCL NDRange).

Tiling: grid (M/bm, N/bn, K/bk), K innermost & sequential; an f32 VMEM
scratch accumulates partial products across K steps so inputs can be
bf16 while accumulation stays f32 (MXU-native).  Block defaults are
MXU-aligned (128 multiples).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int, alpha: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (alpha * acc_ref[...]).astype(o_ref.dtype)


def gemm_pallas(a, b, *, alpha: float = 1.0, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                out_dtype=None, interpret: bool = False):
    """a (M, K) @ b (K, N) -> (M, N).  Shapes padded to block multiples."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    out_dtype = out_dtype or a.dtype
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    nm, nn, nk = -(-M // bm), -(-N // bn), -(-K // bk)
    Mp, Np, Kp = nm * bm, nn * bn, nk * bk
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk, alpha=alpha),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
