"""The Jacobi stencil written into its destination in place.

* ``jacobi_pallas_into`` gives the same bits as the array -> array
  stencil cut out of its row slab and written back by ``kernel_put``,
  over every kind of region the HDArray bridge is handed, in float32
  and bfloat16, with a destination that differs from the source
  everywhere outside the region;
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
from repro.kernels.stencil_hd.kernel import jacobi_pallas, jacobi_pallas_into

ROOT = pathlib.Path(__file__).resolve().parents[1]

# ragged in both axes under (16, 128) blocks: 100 = 6 * 16 + 4 rows,
# 300 = 2 * 128 + 44 columns
M, N = 100, 300
BLOCKS = dict(block_m=16, block_n=128)
SENTINEL = -7.0


def _bands():
    part = Partition.row(0, (M, N), 4, region=Box.make((1, M - 1),
                                                       (1, N - 1)))
    return [part.region(p).bounds for p in range(4)]


def _regions():
    cols = (1, N - 1)
    regions = {"interior": ((1, M - 1), cols),
               "unaligned_start": ((5, M - 1), cols),
               "ragged_last_block": ((97, M - 1), cols),
               "inner_columns": ((20, 40), (130, 250))}
    for p, (rows, cols) in enumerate(_bands()):
        regions[f"band{p}"] = (rows, cols)
        # the one-row boundary boxes halo_split cuts off a rank's band
        regions[f"band{p}_top_row"] = ((rows[0], rows[0] + 1), cols)
        regions[f"band{p}_bottom_row"] = ((rows[1] - 1, rows[1]), cols)
    return regions


REGIONS = _regions()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(REGIONS))
def test_in_place_matches_slice_and_kernel_put(name, dtype):
    (r0, r1), (c0, c1) = rows, cols = REGIONS[name]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((M, N)), dtype)
    dst = jnp.full((M, N), SENTINEL, dtype)
    got = jacobi_pallas_into(x, dst, rows=rows, cols=cols, interpret=True,
                             **BLOCKS)
    sw = jacobi_pallas(x[r0 - 1:r1 + 1, :], interpret=True, **BLOCKS)
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
    assert kernel.paths == hd.JacobiPaths(in_place=0, kernel_put=1)
    assert set(out) == {dst}


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
