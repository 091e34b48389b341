"""Jacobi 5-point stencil Pallas kernel — the per-device compute of the
paper's Jacobi/Convolution benchmarks on TPU.

TPU adaptation: there is no per-thread ghost-zone load like the OpenCL
version.  The grid tiles the array into 2-D ``(bm, bn)`` blocks, and
each block's one-element halo arrives as four thin, tiling-aligned
VMEM tiles fetched by their own BlockSpecs: the last rows of the block
above, the first rows of the block below (``(hr, bn)``, hr = one
sublane tile), and the last/first columns of the blocks to the left and
right (``(bm, hc)``, hc = one lane tile).  Index maps clamp at the
domain edges, where the halo is unused: the first/last global rows and
columns pass through.  So VMEM use depends on the block shape only,
never on the array width, and any row slab the HDArray kernel hands
over (e.g. a ``(4098, 16384)`` band with its halo rows) compiles.  A
ragged last block reads unspecified values past the array end; they
only feed outputs past the end, which are never written.

The HDArray runtime supplies the INTER-DEVICE halo via its planner
(ppermute) — this kernel only handles the intra-device stencil.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _jacobi_kernel(up_ref, mid_ref, dn_ref, lf_ref, rt_ref, o_ref, *,
                   m_true: int, n_true: int):
    i, j = pl.program_id(0), pl.program_id(1)
    bm, bn = mid_ref.shape
    f32 = jnp.float32
    mid = mid_ref[...].astype(f32)
    # neighbors: shift within the block, pulling the edge row/column
    # from the adjacent block's halo tile
    above = jnp.concatenate([up_ref[-1:, :].astype(f32), mid[:-1, :]],
                            axis=0)
    below = jnp.concatenate([mid[1:, :], dn_ref[:1, :].astype(f32)], axis=0)
    left = jnp.concatenate([lf_ref[:, -1:].astype(f32), mid[:, :-1]],
                           axis=1)
    right = jnp.concatenate([mid[:, 1:], rt_ref[:, :1].astype(f32)], axis=1)
    # summation order matches jacobi_ref (left+right+above+below) so the
    # Pallas kernel is BIT-identical to the jnp oracle, not just close;
    # *0.25 == /4 exactly in IEEE (power-of-two divisor)
    out = (left + right + above + below) * 0.25

    # ghost-cell pass-through: global first/last rows and cols keep x
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    edge = ((row == 0) | (row >= m_true - 1)
            | (col == 0) | (col >= n_true - 1))
    o_ref[...] = jnp.where(edge, mid, out).astype(o_ref.dtype)


def jacobi_pallas(x, *, block_m: int = 256, block_n: int = 1024,
                  interpret: bool = False):
    """One Jacobi sweep over x (M, N); edges pass through.

    ``block_m``/``block_n`` must be multiples of the dtype's sublane
    tile (8 rows of 32-bit, 16 of 16-bit) and of 128 lanes whenever the
    array spans more than one block along that axis."""
    M, N = x.shape
    bm, bn = min(block_m, M), min(block_n, N)
    nm, nn = pl.cdiv(M, bm), pl.cdiv(N, bn)
    # halo tile extents: one (sublane, lane) tile, or the whole axis
    hr = min(max(8, 32 // x.dtype.itemsize), M)
    hc = min(128, N)
    if (nm > 1 and bm % hr) or (nn > 1 and bn % hc):
        raise ValueError(f"jacobi_pallas: block ({bm}, {bn}) is not a "
                         f"multiple of the ({hr}, {hc}) halo tile")
    rpb, cpb = bm // hr, bn // hc          # halo tiles per block
    last_r, last_c = pl.cdiv(M, hr) - 1, pl.cdiv(N, hc) - 1

    out = pl.pallas_call(
        functools.partial(_jacobi_kernel, m_true=M, n_true=N),
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((hr, bn), lambda i, j: (jnp.maximum(i * rpb - 1, 0),
                                                 j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((hr, bn), lambda i, j: (
                jnp.minimum((i + 1) * rpb, last_r), j)),
            pl.BlockSpec((bm, hc), lambda i, j: (i, jnp.maximum(j * cpb - 1,
                                                                0))),
            pl.BlockSpec((bm, hc), lambda i, j: (
                i, jnp.minimum((j + 1) * cpb, last_c))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, x, x, x, x)
    return out
