"""Jacobi 5-point stencil Pallas kernel — the per-device compute of the
paper's Jacobi/Convolution benchmarks on TPU.

TPU adaptation: there is no per-thread ghost-zone load like the OpenCL
version.  The grid tiles the array into 2-D ``(bm, bn)`` blocks, and
each block's one-element halo arrives as four thin, tiling-aligned
VMEM tiles fetched by their own BlockSpecs: the last rows of the block
above, the first rows of the block below (``(hr, bn)``, hr = one
sublane tile), and the last/first columns of the blocks to the left and
right (``(bm, hc)``, hc = one lane tile).  Index maps clamp at the
domain edges, where the halo is unused: elements outside the work
region keep their value.  So VMEM use depends on the block shape only,
never on the array width, and any row slab the HDArray kernel hands
over (e.g. a ``(4098, 16384)`` band with its halo rows) compiles.  A
ragged last block reads unspecified values past the array end; they
only feed outputs past the end, which are never written.

Two forms share the kernel body:

* :func:`jacobi_pallas` — array -> array: a new array, first/last rows
  and columns passed through.
* :func:`jacobi_pallas_into` — the sweep over a static interior region
  written straight into a destination buffer, aliased to the output
  (``input_output_aliases``) so no new array is allocated.  The grid
  covers only the blocks the region touches; elements of those blocks
  outside the region keep the destination's own value, which is read
  only in the blocks the region's edge crosses.

The HDArray runtime supplies the INTER-DEVICE halo via its planner
(ppermute) — this kernel only handles the intra-device stencil.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _jacobi_kernel(up_ref, mid_ref, dn_ref, lf_ref, rt_ref, *refs,
                   first, rows, cols):
    # refs is (o_ref,) or (keep_ref, o_ref): what the elements outside
    # the region keep is the input itself, or the destination's block
    *keep_ref, o_ref = refs
    i, j = pl.program_id(0) + first[0], pl.program_id(1) + first[1]
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

    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    inside = ((row >= rows[0]) & (row < rows[1])
              & (col >= cols[0]) & (col < cols[1]))
    keep = keep_ref[0][...].astype(f32) if keep_ref else mid
    o_ref[...] = jnp.where(inside, out, keep).astype(o_ref.dtype)


def _halo_tile(x):
    """One (sublane, lane) tile of ``x``'s dtype, or the whole axis."""
    M, N = x.shape
    return min(max(8, 32 // x.dtype.itemsize), M), min(128, N)


def _sweep(x, keep, *, bm, bn, first, grid, rows, cols, interpret):
    """The ``jacobi_step`` pallas_call over ``grid`` blocks of
    ``(bm, bn)`` from block ``first`` on: the stencil of ``x`` inside
    ``rows`` x ``cols``; outside, ``x`` itself (``keep`` None) or
    ``keep``, which is then aliased to the output."""
    M, N = x.shape
    hr, hc = _halo_tile(x)
    if (pl.cdiv(M, bm) > 1 and bm % hr) or (pl.cdiv(N, bn) > 1 and bn % hc):
        raise ValueError(f"jacobi_pallas: block ({bm}, {bn}) is not a "
                         f"multiple of the ({hr}, {hc}) halo tile")
    rpb, cpb = bm // hr, bn // hc          # halo tiles per block
    last_r, last_c = pl.cdiv(M, hr) - 1, pl.cdiv(N, hc) - 1
    i0, j0 = first

    in_specs = [
        pl.BlockSpec((hr, bn), lambda i, j: (
            jnp.maximum((i + i0) * rpb - 1, 0), j + j0)),
        pl.BlockSpec((bm, bn), lambda i, j: (i + i0, j + j0)),
        pl.BlockSpec((hr, bn), lambda i, j: (
            jnp.minimum((i + i0 + 1) * rpb, last_r), j + j0)),
        pl.BlockSpec((bm, hc), lambda i, j: (
            i + i0, jnp.maximum((j + j0) * cpb - 1, 0))),
        pl.BlockSpec((bm, hc), lambda i, j: (
            i + i0, jnp.minimum((j + j0 + 1) * cpb, last_c))),
    ]
    args = [x] * 5
    aliases = {}
    if keep is not None:
        (r0, r1), (c0, c1) = rows, cols

        def keep_block(i, j):
            # a block wholly inside the region needs none of keep: it
            # names the first block of its row, the one fetched last
            # (or about to be), so the pipeline skips the fetch
            bi, bj = i + i0, j + j0
            inner = ((bi * bm >= r0) & ((bi + 1) * bm <= r1)
                     & (bj * bn >= c0) & ((bj + 1) * bn <= c1))
            return bi, jnp.where(inner, j0, bj)

        in_specs.append(pl.BlockSpec((bm, bn), keep_block))
        args.append(keep)
        aliases = {5: 0}

    return pl.pallas_call(
        functools.partial(_jacobi_kernel, first=first, rows=rows,
                          cols=cols),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i + i0, j + j0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # chipbench/metrics/stencil_roofline.py finds the kernel in a
        # device trace by this name (%jacobi_step.N)
        name="jacobi_step",
    )(*args)


def jacobi_pallas(x, *, block_m: int = 256, block_n: int = 1024,
                  interpret: bool = False):
    """One Jacobi sweep over x (M, N); edges pass through.

    ``block_m``/``block_n`` must be multiples of the dtype's sublane
    tile (8 rows of 32-bit, 16 of 16-bit) and of 128 lanes whenever the
    array spans more than one block along that axis."""
    M, N = x.shape
    bm, bn = min(block_m, M), min(block_n, N)
    return _sweep(x, None, bm=bm, bn=bn, first=(0, 0),
                  grid=(pl.cdiv(M, bm), pl.cdiv(N, bn)),
                  rows=(1, M - 1), cols=(1, N - 1), interpret=interpret)


def jacobi_pallas_into(x, dst, *, rows, cols, block_m: int = 256,
                       block_n: int = 1024, interpret: bool = False):
    """One Jacobi sweep of ``x`` over the static interior region
    ``rows`` x ``cols``, written into ``dst`` in place: returns ``dst``
    with the region replaced by the stencil of ``x`` and every other
    element as it was in ``dst``.  Blocks shrink to the region's
    extent (down to one halo tile), so a one-row boundary box costs a
    row of thin blocks, not a row of full ones."""
    M, N = x.shape
    (r0, r1), (c0, c1) = rows, cols
    if dst.shape != x.shape or dst.dtype != x.dtype:
        raise ValueError("jacobi_pallas_into: dst must match x's shape "
                         "and dtype")
    if not (1 <= r0 < r1 <= M - 1 and 1 <= c0 < c1 <= N - 1):
        raise ValueError(f"jacobi_pallas_into: region {rows} x {cols} is "
                         f"not a non-empty interior region of {x.shape}")
    hr, hc = _halo_tile(x)
    bm = min(block_m, M, pl.cdiv(r1 - r0, hr) * hr)
    bn = min(block_n, N, pl.cdiv(c1 - c0, hc) * hc)
    first = (r0 // bm, c0 // bn)
    grid = ((r1 - 1) // bm - first[0] + 1, (c1 - 1) // bn - first[1] + 1)
    return _sweep(x, dst, bm=bm, bn=bn, first=first, grid=grid,
                  rows=(r0, r1), cols=(c0, c1), interpret=interpret)
