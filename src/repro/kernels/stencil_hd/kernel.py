"""Jacobi 5-point stencil Pallas kernel — the per-device compute of the
paper's Jacobi/Convolution benchmarks on TPU.

TPU adaptation: there is no per-thread ghost-zone load like the OpenCL
version.  The grid tiles the array into 2-D ``(bm, bn)`` blocks, and
each grid step fetches from HBM:

* the source block ``(bm, bn)``;
* its one-element halo as four thin, tiling-aligned tiles: the last
  rows of the block above and the first rows of the block below
  (``(hr, bn)``, hr = one sublane tile), and the last/first columns of
  the blocks to the left and right (``(bm, hc)``, hc = one lane tile).
  Index maps clamp at the domain edges, where the halo is unused;
* in the in-place form, and only in the blocks the region's edge
  crosses, up to four strips of the destination: ``(s, bn)`` at the
  top and bottom, ``(bm, s)`` at the left and right, each the
  narrowest aligned strip (one halo tile for the HDArray regions) that
  holds the block's elements outside the region, cut to the array.
  The destination is one operand, left in HBM and aliased to the
  output (a second operand of the donated buffer would make XLA copy
  it), so the strips are copied by hand, beside the body; other blocks
  copy nothing.  A copy's window must be whole tiles, so an array
  that is not (sublane, lane)-tile aligned reads the destination's
  whole block instead, pipelined, in the blocks the region's edge
  crosses.

The output block is the stencil, and the elements outside the region
are then taken from the strips (from the source block itself in the
array form).  ``(hr + hr) / bm + (hc + hc) / bn`` of the counted bytes
go to the halos, so the default block is wide in lanes: ``(256,
4096)``, cut to the array and, in place, to the region's extent; arrays
up to 1024 columns fetch as before.  VMEM use depends on the block
shape only, never on the array width, so any row slab the HDArray
kernel hands over (e.g. a ``(4098, 16384)`` band with its halo rows)
compiles, and ``vmem_limit_bytes`` is set from what the call allocates
(:meth:`SweepPlan.vmem_bytes`).  A ragged last block reads unspecified
values past the array end; they only feed outputs past the end, which
are never written.

Two forms share the kernel body:

* :func:`jacobi_pallas` — array -> array: a new array, first/last rows
  and columns passed through.
* :func:`jacobi_pallas_into` — the sweep over a static interior region
  written straight into a destination buffer, aliased to the output
  (``input_output_aliases``) so no new array is allocated.  The grid
  covers only the blocks the region touches; elements of those blocks
  outside the region keep the destination's own value.

:meth:`SweepPlan.fetches` counts what a sweep fetches beyond the
source blocks, from the same index maps and strips.

The HDArray runtime supplies the INTER-DEVICE halo via its planner
(ppermute) — this kernel only handles the intra-device stencil.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_M, BLOCK_N = 256, 4096
# the body's float32 temporaries, in blocks (a (256, 4096) float32
# sweep compiles for v5e with 24 MiB: its buffers and about one block),
# headroom above that, and the compiler's own default limit on v5e
_BODY_BLOCKS = 3
_VMEM_HEADROOM = 4 << 20
_VMEM_DEFAULT = 16 << 20


def _tile(dtype):
    """One (sublane, lane) tile of ``dtype``."""
    return max(8, 32 // jnp.dtype(dtype).itemsize), 128


def _halo_tile(shape, dtype):
    """One tile of ``dtype``, or the whole axis where it is shorter."""
    return tuple(map(min, _tile(dtype), shape))


def _strip(lo, hi, block, tile):
    """``(s, k)``: the narrowest aligned strip that holds ``[lo, hi)``,
    which lies in one block: ``s`` a multiple of ``tile`` that divides
    ``block`` (else the whole block), ``k`` its index in strips of
    ``s``."""
    for s in range(tile, block, tile):
        if block % s == 0 and hi <= (lo // s + 1) * s:
            return s, lo // s
    return block, lo // block


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """One sweep's grid and what each grid step fetches
    (:func:`sweep_plan`).

    ``strips`` are ``(axis, at_end, s, k)``: the ``k``-th strip of ``s``
    rows (axis 0) or lanes (axis 1), holding the elements outside the
    region in the first (``at_end`` False) or last block along that
    axis.  ``keep`` says where they are read: ``"source"``, the source
    block (the array form); ``"strips"``, the destination, copied by
    hand; ``"blocks"``, the destination's whole block, pipelined."""
    shape: tuple
    dtype: np.dtype
    block: tuple
    first: tuple
    grid: tuple
    rows: tuple
    cols: tuple
    strips: tuple
    keep: str

    def halos(self):
        """``(block shape, index map)`` of the up, down, left and right
        halo tiles; an index map takes the array module first."""
        (M, N), (bm, bn), (i0, j0) = self.shape, self.block, self.first
        hr, hc = _halo_tile(self.shape, self.dtype)
        rpb, cpb = bm // hr, bn // hc          # halo tiles per block
        last_r, last_c = pl.cdiv(M, hr) - 1, pl.cdiv(N, hc) - 1
        return [
            ((hr, bn), lambda xp, i, j: (
                xp.maximum((i + i0) * rpb - 1, 0), j + j0)),
            ((hr, bn), lambda xp, i, j: (
                xp.minimum((i + i0 + 1) * rpb, last_r), j + j0)),
            ((bm, hc), lambda xp, i, j: (
                i + i0, xp.maximum((j + j0) * cpb - 1, 0))),
            ((bm, hc), lambda xp, i, j: (
                i + i0, xp.minimum((j + j0 + 1) * cpb, last_c))),
        ]

    def strip_shape(self, n):
        """The VMEM buffer of strip ``n``: ``(s, bn)`` or ``(bm, s)``."""
        axis, _, s, _ = self.strips[n]
        return (s, self.block[1]) if axis == 0 else (self.block[0], s)

    def strip_copies(self, n, i, j):
        """``(condition, source window, buffer part)`` of strip ``n``'s
        copy from the destination in block ``(i, j)``: a block's extent
        along the other axis, shorter at the array's end."""
        axis, _, s, k = self.strips[n]
        o = 1 - axis
        a, sa = k * s, min(s, self.shape[axis] - k * s)
        size, b = self.block[o], (i, j)[o]
        last = self.first[o] + self.grid[o] - 1
        tail = self.shape[o] - last * size        # past the last block
        cases = [(b < last if tail < size else True, pl.ds(b * size, size),
                  size)]
        if tail < size:
            cases.append((b == last, pl.ds(last * size, tail), tail))
        for cond, win, w in cases:
            yield cond, *(((pl.ds(a, sa), win), (pl.ds(0, sa), pl.ds(0, w)))
                          if axis == 0 else
                          ((win, pl.ds(a, sa)), (pl.ds(0, w), pl.ds(0, sa))))

    def keep_block(self):
        """``(block shape, index map)`` of the destination's whole
        block (``keep == "blocks"``).  A block wholly inside the region
        names the first block of its row, the one fetched last (or
        about to be), so the pipeline skips the fetch."""
        (bm, bn), (i0, j0) = self.block, self.first
        (r0, r1), (c0, c1) = self.rows, self.cols

        def index(xp, i, j):
            bi, bj = i + i0, j + j0
            inner = ((bi * bm >= r0) & ((bi + 1) * bm <= r1)
                     & (bj * bn >= c0) & ((bj + 1) * bn <= c1))
            return bi, xp.where(inner, j0, bj)

        return (bm, bn), index

    def fetches(self):
        """``(halo, keep)`` bytes one sweep fetches.  A pipelined block
        is fetched at the first grid step and wherever its index map
        names another block than the step before (the grid runs
        row-major; a ragged block counts whole); a strip of the
        destination once in each block along its edge of the grid, cut
        to the array."""
        gi, gj = self.grid
        i, j = np.divmod(np.arange(gi * gj), gj)
        itemsize = jnp.dtype(self.dtype).itemsize

        def pipelined(specs):
            total = 0
            for shape, index in specs:
                idx = np.stack([np.broadcast_to(v, i.shape)
                                for v in index(np, i, j)])
                steps = 1 + np.count_nonzero(
                    np.any(idx[:, 1:] != idx[:, :-1], axis=0))
                total += steps * shape[0] * shape[1] * itemsize
            return total

        keep = pipelined([self.keep_block()]) if self.keep == "blocks" else 0
        by_hand = self.strips if self.keep == "strips" else ()
        for n, (axis, *_) in enumerate(by_hand):
            f, g = self.first[1 - axis], self.grid[1 - axis]
            for b in range(f, f + g):
                keep += sum(r.size * c.size * itemsize for live, _, (r, c)
                            in self.strip_copies(n, b, b) if live)
        return pipelined(self.halos()), keep

    def vmem_bytes(self):
        """VMEM the call allocates: the pipelined blocks twice (the
        pipeline's double buffers), the strips' buffers, the body's
        float32 temporaries and headroom; never under the compiler's
        default."""
        bm, bn = self.block
        blocks = (2 + (self.keep == "blocks")) * bm * bn + sum(
            r * c for (r, c), _ in self.halos())
        strips = sum(r * c for r, c in map(self.strip_shape,
                                           range(len(self.strips))))
        buffers = jnp.dtype(self.dtype).itemsize * (
            2 * blocks + (strips if self.keep == "strips" else 0))
        return max(buffers + _BODY_BLOCKS * 4 * bm * bn + _VMEM_HEADROOM,
                   _VMEM_DEFAULT)


def sweep_plan(shape, dtype, *, rows=None, cols=None,
               block_m: int = BLOCK_M, block_n: int = BLOCK_N):
    """The plan of :func:`jacobi_pallas` over an array of ``shape`` and
    ``dtype``; with ``rows``/``cols``, of :func:`jacobi_pallas_into`
    over that interior region, its blocks shrunk to the region's extent
    (down to one halo tile) and its grid covering only the blocks the
    region touches."""
    M, N = shape
    hr, hc = _halo_tile(shape, dtype)
    if rows is not None:
        (r0, r1), (c0, c1) = rows, cols
        if not (1 <= r0 < r1 <= M - 1 and 1 <= c0 < c1 <= N - 1):
            raise ValueError(f"jacobi_pallas_into: region {rows} x {cols} "
                             f"is not a non-empty interior region of "
                             f"{shape}")
        bm = min(block_m, M, pl.cdiv(r1 - r0, hr) * hr)
        bn = min(block_n, N, pl.cdiv(c1 - c0, hc) * hc)
        first = (r0 // bm, c0 // bn)
        grid = ((r1 - 1) // bm - first[0] + 1,
                (c1 - 1) // bn - first[1] + 1)
        tr, tc = _tile(dtype)
        keep = "strips" if M % tr == 0 and N % tc == 0 else "blocks"
    else:
        (r0, r1), (c0, c1) = (1, M - 1), (1, N - 1)
        bm, bn = min(block_m, M), min(block_n, N)
        first, grid = (0, 0), (pl.cdiv(M, bm), pl.cdiv(N, bn))
        keep = "source"
    if (pl.cdiv(M, bm) > 1 and bm % hr) or (pl.cdiv(N, bn) > 1 and bn % hc):
        raise ValueError(f"jacobi_pallas: block ({bm}, {bn}) is not a "
                         f"multiple of the ({hr}, {hc}) halo tile")
    # the elements outside the region: before it in the first block
    # along an axis, after it (up to the array's end) in the last
    strips = []
    for axis, (lo, hi), b, size, tile in ((0, (r0, r1), bm, M, hr),
                                          (1, (c0, c1), bn, N, hc)):
        start, stop = first[axis] * b, (first[axis] + grid[axis]) * b
        for at_end, (a, z) in ((False, (start, lo)),
                               (True, (hi, min(stop, size)))):
            if a >= z:
                continue
            strip = (axis, at_end, *_strip(a, z, b, tile))
            # one block along the axis: its two strips may coincide
            if not (grid[axis] == 1 and strips and strips[-1][0] == axis
                    and strips[-1][2:] == strip[2:]):
                strips.append(strip)
    return SweepPlan(tuple(shape), jnp.dtype(dtype), (bm, bn), first,
                     grid, (r0, r1), (c0, c1), tuple(strips), keep)


def _jacobi_kernel(up_ref, mid_ref, dn_ref, lf_ref, rt_ref, *refs, plan):
    # refs is (o_ref,), (dst_hbm, o_ref, *strip_bufs, sems) or
    # (keep_ref, o_ref) by plan.keep: what the elements outside the
    # region keep is the source block itself, the destination's strips
    # copied from HBM, or the destination's block
    by_hand = plan.keep == "strips"
    if by_hand:
        dst_hbm, o_ref, *bufs, sems = refs
    else:
        *keep_ref, o_ref = refs
        block = keep_ref[0] if keep_ref else mid_ref
    (i0, j0), (bm, bn), (gi, gj) = plan.first, plan.block, plan.grid
    (r0, r1), (c0, c1) = plan.rows, plan.cols
    i, j = pl.program_id(0) + i0, pl.program_id(1) + j0
    strips = []
    for n, (axis, at_end, s, k) in enumerate(plan.strips):
        edge = (gi, gj)[axis] - 1 if at_end else 0
        off = k * s - ((i0, j0)[axis] + edge) * (bm, bn)[axis]
        live = pl.program_id(axis) == edge
        copies = [(cond, pltpu.make_async_copy(dst_hbm.at[win],
                                               bufs[n].at[part], sems.at[n]))
                  for cond, win, part in plan.strip_copies(n, i, j)
                  ] if by_hand else []
        strips.append((axis, off, s, live, copies))

    def each_copy(live, copies, act):
        @pl.when(live)
        def _():
            for cond, copy in copies:
                pl.when(cond)(getattr(copy, act))

    # the strips' copies run beside the body; strips of a block are read
    # before the body, which may then reuse the source block's buffer
    for axis, off, s, live, copies in strips:
        each_copy(live, copies, "start")
    kept = [] if by_hand else [
        block[(slice(off, off + s), slice(None)) if axis == 0
              else (slice(None), slice(off, off + s))]
        for axis, off, s, *_ in strips]

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
    o_ref[...] = out.astype(o_ref.dtype)

    # every element outside the region lies in a strip used in its block
    for n, (axis, off, s, live, copies) in enumerate(strips):
        each_copy(live, copies, "wait")

        @pl.when(live)
        def _(n=n, axis=axis, off=off, s=s):
            rs, cs = (s, bn) if axis == 0 else (bm, s)
            roff, coff = (off, 0) if axis == 0 else (0, off)
            sl = (pl.ds(roff, rs), pl.ds(coff, cs))
            row = i * bm + roff + jax.lax.broadcasted_iota(jnp.int32,
                                                           (rs, 1), 0)
            col = j * bn + coff + jax.lax.broadcasted_iota(jnp.int32,
                                                           (1, cs), 1)
            inside = ((row >= r0) & (row < r1) & (col >= c0) & (col < c1))
            keep = bufs[n][...] if by_hand else kept[n]
            o_ref[sl] = jnp.where(inside, o_ref[sl], keep)


def _sweep(plan, x, dst, *, interpret):
    """The ``jacobi_step`` pallas_call of ``plan`` over ``x``; with
    ``dst``, the destination, aliased to the output and passed once
    (a second operand of the donated buffer would make XLA copy it)."""
    bm, bn = plan.block
    i0, j0 = plan.first
    in_specs = [pl.BlockSpec(shape, functools.partial(index, jnp))
                for shape, index in plan.halos()]
    in_specs.insert(1, pl.BlockSpec((bm, bn), lambda i, j: (i + i0, j + j0)))
    args, aliases, scratch = [x] * 5, {}, []
    if dst is not None:
        args.append(dst)
        aliases = {5: 0}
    if plan.keep == "blocks":
        shape, index = plan.keep_block()
        in_specs.append(pl.BlockSpec(shape, functools.partial(index, jnp)))
    elif plan.keep == "strips":
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch = [pltpu.VMEM(plan.strip_shape(n), x.dtype)
                   for n in range(len(plan.strips))]
        scratch.append(pltpu.SemaphoreType.DMA((max(len(scratch), 1),)))

    return pl.pallas_call(
        functools.partial(_jacobi_kernel, plan=plan),
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i + i0, j + j0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=plan.vmem_bytes()),
        interpret=interpret,
        # chipbench/metrics/stencil_roofline.py finds the kernel in a
        # device trace by this name (%jacobi_step.N)
        name="jacobi_step",
    )(*args)


def jacobi_pallas(x, *, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                  interpret: bool = False):
    """One Jacobi sweep over x (M, N); edges pass through.

    ``block_m``/``block_n`` must be multiples of the dtype's sublane
    tile (8 rows of 32-bit, 16 of 16-bit) and of 128 lanes whenever the
    array spans more than one block along that axis."""
    plan = sweep_plan(x.shape, x.dtype, block_m=block_m, block_n=block_n)
    return _sweep(plan, x, None, interpret=interpret)


def jacobi_pallas_into(x, dst, *, rows, cols, block_m: int = BLOCK_M,
                       block_n: int = BLOCK_N, interpret: bool = False):
    """One Jacobi sweep of ``x`` over the static interior region
    ``rows`` x ``cols``, written into ``dst`` in place: returns ``dst``
    with the region replaced by the stencil of ``x`` and every other
    element as it was in ``dst``.  Blocks shrink to the region's
    extent (down to one halo tile), so a one-row boundary box costs a
    row of thin blocks, not a row of full ones."""
    if dst.shape != x.shape or dst.dtype != x.dtype:
        raise ValueError("jacobi_pallas_into: dst must match x's shape "
                         "and dtype")
    plan = sweep_plan(x.shape, x.dtype, rows=tuple(rows),
                      cols=tuple(cols), block_m=block_m, block_n=block_n)
    return _sweep(plan, x, dst, interpret=interpret)

