"""The Jacobi stencil written into its destination in place.

* ``jacobi_pallas_into`` gives the same bits as the array -> array
  stencil cut out of its row slab and written back by ``kernel_put``,
  over every kind of region the HDArray bridge is handed, in float32
  and bfloat16, under one-lane-tile and wider blocks, with a
  destination that differs from the source everywhere outside the
  region;
* what the stencil fetches beyond its source blocks at 16384^2 (the
  bridge's ``halo_bytes`` and ``keep_bytes``) stays a small share of
  the 8 bytes an element that its roofline counts;
* the bridge counts which path each call took: the benchmark's
  ping-pong pipeline takes the in-place path on every trace, an
  ``impl="ref"`` kernel (or one whose source is its destination) the
  ``kernel_put`` path;
* the in-place ``pallas_call`` aliases the destination to its output.
"""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Box
from repro.core.partition import Partition
from repro.executors.kernels import kernel_put
from repro.kernels import hd
from repro.kernels.stencil_hd.kernel import (jacobi_pallas, jacobi_pallas_into,
                                             sweep_plan)
from repro.kernels.stencil_hd.ref import jacobi_ref

ROOT = pathlib.Path(__file__).resolve().parents[1]

# "ragged" is ragged in both axes under (16, 128) blocks: 100 = 6 * 16
# + 4 rows, 300 = 2 * 128 + 44 columns, so the stencil reads the
# destination's whole blocks; "tiled" is whole (sublane, lane) tiles, so
# it copies strips of the destination.  (16, 256) blocks span two lane
# tiles, so the left and right strips are narrower than a block.
SHAPES = {"ragged": (100, 300), "tiled": (96, 384)}
M, N = SHAPES["ragged"]
BLOCKS = [dict(block_m=16, block_n=128), dict(block_m=16, block_n=256)]
SENTINEL = -7.0


def _bands(M, N):
    part = Partition.row(0, (M, N), 4, region=Box.make((1, M - 1),
                                                       (1, N - 1)))
    return [part.region(p).bounds for p in range(4)]


def _regions(M, N):
    cols = (1, N - 1)
    regions = {"interior": ((1, M - 1), cols),
               "unaligned_start": ((5, M - 1), cols),
               "ragged_last_block": ((M - 3, M - 1), cols),
               # the rows outside it in its first block are wider than
               # a halo tile
               "wide_outside_top": ((27, M - 1), cols),
               "inner_columns": ((20, 40), (130, 250))}
    for p, (rows, cols) in enumerate(_bands(M, N)):
        regions[f"band{p}"] = (rows, cols)
        # the one-row boundary boxes halo_split cuts off a rank's band
        regions[f"band{p}_top_row"] = ((rows[0], rows[0] + 1), cols)
        regions[f"band{p}_bottom_row"] = ((rows[1] - 1, rows[1]), cols)
    return regions


REGIONS = _regions(M, N)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("blocks", BLOCKS,
                         ids=lambda b: f"{b['block_m']}x{b['block_n']}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(REGIONS))
def test_in_place_matches_slice_and_kernel_put(name, dtype, blocks, shape):
    M, N = SHAPES[shape]
    (r0, r1), (c0, c1) = rows, cols = _regions(M, N)[name]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((M, N)), dtype)
    dst = jnp.full((M, N), SENTINEL, dtype)
    plan = sweep_plan((M, N), dtype, rows=rows, cols=cols, **blocks)
    assert plan.keep == {"tiled": "strips", "ragged": "blocks"}[shape]
    got = jacobi_pallas_into(x, dst, rows=rows, cols=cols, interpret=True,
                             **blocks)
    slab = x[r0 - 1:r1 + 1, :]
    sw = jacobi_pallas(slab, interpret=True, **blocks)
    # the stencil sums in float32 whatever the storage type
    ref = jacobi_ref(slab.astype(jnp.float32)).astype(dtype)
    np.testing.assert_array_equal(np.asarray(sw, np.float32),
                                  np.asarray(ref, np.float32))
    want = kernel_put(dst, (slice(r0, r1), slice(c0, c1)),
                      sw[1:-1, c0:c1])
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    outside = np.ones((M, N), bool)
    outside[r0:r1, c0:c1] = False
    assert np.all(np.asarray(got, np.float32)[outside] == SENTINEL)


@pytest.mark.parametrize("rows,cols", [((0, M - 1), (1, N - 1)),
                                       ((1, M), (1, N - 1)),
                                       ((1, M - 1), (0, N - 1)),
                                       ((3, 3), (1, N - 1))])
def test_in_place_refuses_a_region_that_is_not_interior(rows, cols):
    x = jnp.zeros((M, N), jnp.float32)
    with pytest.raises(ValueError, match="interior"):
        jacobi_pallas_into(x, x, rows=rows, cols=cols, interpret=True)


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} host devices (XLA_FLAGS not applied?)")


@pytest.fixture
def accelerator_shapes(monkeypatch):
    """The one-program step and the per-rank ``lax.switch`` over
    ``halo_split``'s boxes, as on the chip (see test_capture)."""
    from repro.executors import jax_exec

    monkeypatch.setattr(jax_exec, "cpu_host_platform", lambda: False)


def _pipeline_paths(p):
    """Build the benchmark's Jacobi pipeline on ``p`` CPU devices, run
    one call and return its Jacobi kernels' path counts, and whether
    both arrays equal the reference."""
    sys.path[:0] = [str(ROOT)]
    try:
        from chipbench.drivers import hdarray_pipeline as bench
    finally:
        sys.path.remove(str(ROOT))
    config = {"grid_rows": 64, "grid_cols": 256, "devices": p}
    traffic = {"sweeps_per_call": 10}
    x0 = bench.make_grid(config, 11)
    rt, ha, hb, steps = bench.build(config, traffic, x0, interpret=True)
    kernels = {id(st["kernel"]): st["kernel"] for st in steps}.values()
    rt.run_pipeline(steps)
    paths = (sum(k.paths.in_place for k in kernels),
             sum(k.paths.kernel_put for k in kernels))
    got = rt.read_coherent(ha), rt.read_coherent(hb)
    rt.close()
    want = bench.reference(config, 11, 10, jax.devices()[:p])
    return paths, all(np.array_equal(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("p", [1, 4])
def test_benchmark_pipeline_takes_the_in_place_path(p):
    _need_devices(p)
    (in_place, put), exact = _pipeline_paths(p)
    assert in_place > 0 and put == 0
    assert exact


def test_benchmark_pipeline_in_place_over_halo_split_boxes(
        accelerator_shapes):
    _need_devices(4)
    (in_place, put), exact = _pipeline_paths(4)
    # every rank's interior box and boundary rows, in fused steps and
    # in the captured scan
    assert in_place > 4 and put == 0
    assert exact


@pytest.mark.parametrize("impl,src,dst", [("ref", "A", "B"),
                                          ("pallas", "A", "A")])
def test_bridge_falls_back_to_kernel_put(impl, src, dst):
    kernel = hd.make_jacobi_kernel(src, dst, impl=impl)
    rng = np.random.default_rng(2)
    bufs = {"A": jnp.asarray(rng.random((32, 256), dtype=np.float32)),
            "B": jnp.zeros((32, 256), jnp.float32)}
    out = kernel(Box.make((1, 31), (1, 255)), bufs)
    # the Pallas stencil over the (32, 256) slab fetches its halo tiles
    # once; nothing is read from a destination
    halo = 2 * (8 * 256 + 32 * 128) * 4 if impl == "pallas" else 0
    assert kernel.paths == hd.JacobiPaths(in_place=0, kernel_put=1,
                                          halo_bytes=halo, keep_bytes=0)
    assert set(out) == {dst}


def _rank_boxes(n, p):
    """Per rank, the boxes the fused steps sweep at ``n``^2 over ``p``
    ranks by rows: its band's interior and the one-row boxes next to a
    neighbour's halo rows."""
    part = Partition.row(0, (n, n), p, region=Box.make((1, n - 1),
                                                       (1, n - 1)))
    for r in range(p):
        (r0, r1), cols = part.region(r).bounds
        top, bottom = r0 + (r > 0), r1 - (r < p - 1)
        yield (r1 - r0) * (cols[1] - cols[0]), (
            [((top, bottom), cols)] + [((r0, top), cols)] * (top > r0)
            + [((bottom, r1), cols)] * (r1 > bottom))


@pytest.mark.parametrize("p", [1, 4])
def test_stencil_fetches_little_beyond_its_counted_bytes(p):
    """At the benchmark's 16384^2 float32 the halos cost at most 7% of
    the 8 bytes an element the roofline counts (the parent's (256,
    1024) blocks: 15.6%) and the destination's strips at most 1.5%
    (its whole edge blocks: 7.6% on one rank, 11.7% on four)."""
    n = 16384
    sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
    for elements, boxes in _rank_boxes(n, p):
        kernel = hd.make_jacobi_kernel("A", "B", impl="pallas")
        for rows, cols in boxes:
            jax.eval_shape(lambda a, b: kernel(Box.make(rows, cols),
                                               {"A": a, "B": b}), sds, sds)
        paths, counted = kernel.paths, 8 * elements
        assert paths.in_place == len(boxes) and paths.kernel_put == 0
        assert 0 < paths.halo_bytes <= 0.07 * counted
        assert 0 < paths.keep_bytes <= 0.015 * counted


def _pallas_aliases(jaxpr):
    """``input_output_aliases`` of every pallas_call in ``jaxpr``,
    nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["input_output_aliases"])
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                found += _pallas_aliases(sub)
            elif hasattr(v, "eqns"):
                found += _pallas_aliases(v)
    return found


def test_in_place_pallas_call_aliases_dst():
    kernel = hd.make_jacobi_kernel("A", "B", impl="pallas")
    region = Box.make((1, 31), (1, 255))
    x = jnp.zeros((32, 256), jnp.float32)
    closed = jax.make_jaxpr(
        lambda a, b: kernel(region, {"A": a, "B": b})["B"])(x, x)
    aliases = _pallas_aliases(closed.jaxpr)
    assert len(aliases) == 1 and aliases[0]
