"""Compile rehearsals for a described TPU v5e chip.

The TPU compiler that ships with libtpu compiles for a chip described
by topology name, with no chip attached.  These tests compile the main
path's kernels and the serving programs at their real sizes, so they
catch what interpret-mode Pallas and XLA:CPU cannot: Mosaic's tiling
rules, the VMEM limit, and programs that do not fit the chip's HBM.
Nothing runs: a compile that passes says nothing about results or
speed.

Only one process at a time may load libtpu, so the topology is
described inside a module fixture, never while a module is imported,
and every test that needs it lives in this one file.  Where it cannot
be described (no libtpu) the tests skip.  The persistent compilation
cache is off around these compiles: an entry written for a described
chip cannot be read back without one.
"""
import dataclasses
import importlib.util
import os
import pathlib
import sys

import pytest

import jax
import jax.numpy as jnp

V5E_HBM = 16 * 2 ** 30
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gemm_pallas_compiles_4096(one_chip):
    from repro.kernels.gemm_hd.kernel import gemm_pallas

    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=one_chip)
    assert _has_kernel(jax.jit(gemm_pallas).lower(x, x).compile())


# the whole array on one chip, and one of four row slabs (a 4096-row
# band plus its two halo rows) as the HDArray Jacobi kernel passes it
@pytest.mark.parametrize("shape", [(16384, 16384), (4098, 16384)])
def test_jacobi_pallas_compiles(one_chip, shape):
    from repro.kernels.stencil_hd.kernel import jacobi_pallas

    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(jacobi_pallas).lower(x).compile()
    assert _has_kernel(compiled)


# the sweep written into a donated destination, over the regions the
# HDArray Jacobi bridge hands it at 16384^2: the whole interior on one
# chip, a rank's band interior on four, and a one-row boundary box.
# The destination's buffer is the output's: aliased, never copied.
@pytest.mark.parametrize("rows", [(1, 16383), (4097, 8191), (4095, 4096)])
def test_jacobi_in_place_compiles_aliased(one_chip, rows):
    import re

    from repro.kernels.stencil_hd.kernel import jacobi_pallas_into

    n = 16384
    x = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda a, d: jacobi_pallas_into(a, d, rows=rows,
                                                 cols=(1, n - 1)),
                 donate_argnums=1)
    compiled = fn.lower(x, x).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * n * n
    assert not re.search(r"\bcopy\(", compiled.as_text())


@pytest.mark.parametrize("kv_heads,window", [(32, None), (8, 1024)])
def test_flash_pallas_compiles(one_chip, kv_heads, window):
    from functools import partial

    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    B, T, H, D = 4, 2048, 32, 128

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(partial(flash_attention_pallas, window=window,
                         block_q=512, block_kv=1024))
    compiled = fn.lower(sd((B, T, H, D)), sd((B, T, kv_heads, D)),
                        sd((B, T, kv_heads, D)),
                        qpos=sd((B, T), jnp.int32)).compile()
    assert _has_kernel(compiled)


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke.py (repository root), for its serving configuration."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chip_smoke", mod)  # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serving_programs_fit_one_chip(one_chip, smoke, program):
    from repro.configs import get_config
    from repro.models import build
    from repro.serve.engine import make_decode_step, make_prefill_step

    size = smoke.ServeSize()
    cfg = dataclasses.replace(get_config(size.arch), n_layers=size.layers)
    bundle = build(cfg)
    params = _on(one_chip, jax.eval_shape(
        lambda: bundle.init(jax.random.PRNGKey(0))[0]))
    cache = _on(one_chip, jax.eval_shape(
        lambda: bundle.init_cache(size.slots, size.max_seq)))
    i32 = jnp.int32
    if program == "prefill":          # the engine prefills per chunk
        fn = make_prefill_step(bundle)
        chunk = min(size.prompt_len, size.prefill_chunk)
        batch = {"tokens": (size.slots, chunk)}
    else:
        fn = make_decode_step(bundle)
        batch = {"token": (size.slots, 1), "pos": (size.slots,)}
    batch = {k: jax.ShapeDtypeStruct(s, i32, sharding=one_chip)
             for k, s in batch.items()}
    m = jax.jit(fn).lower(params, batch, cache).compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert total <= V5E_HBM
    # the budget chip_smoke.py holds the engine to: the program plus
    # the engine's extra cache copy
    assert total + cache_bytes <= smoke.FIT_BYTES
