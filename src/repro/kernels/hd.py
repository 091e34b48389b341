"""HDArray device-kernel factories for the real compute kernels.

The kernel packages (``gemm_hd`` / ``stencil_hd`` / ``flash_attention``)
expose jitted *array -> array* ops.  The runtime, though, calls OpenCL-
style per-device kernels — ``kernel(region, bufs) -> {name: buffer}``
(the :func:`~repro.executors.kernels.device_kernel` convention).  The
factories here bridge the two: each returns a device kernel that slices
its work region out of the full per-device buffers, runs the REAL op
(Pallas on TPU, interpret-mode Pallas or the jnp oracle elsewhere —
pick with ``impl=``), and writes the result back functionally.  The
Jacobi bridge's Pallas stencil writes an interior region straight into
its destination buffer instead (see :func:`make_jacobi_kernel`).

Because the result is a ``device_kernel``, the resident jax backend
traces it into its fused one-program steps (exchange + compute in a
single jitted shard_map program, ``Executor.execute_step``) and into
captured steady-state ``lax.scan`` pipelines — so the paper's
benchmarks run their actual tile kernels inside ONE XLA program per
step instead of the jnp reference on the host path.  On sim the same
source runs against numpy mirrors, bit-identically.

Create ONE kernel per pipeline and reuse it across steps: each factory
call returns a fresh function object, which is a fresh program-cache
key on the executor.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.executors.kernels import device_kernel, kernel_put


@dataclass
class JacobiPaths:
    """Cumulative count of the path a Jacobi bridge kernel took — once
    per trace on the jax backend, once per call on sim; read as deltas
    like ``PlannerStats``.  ``in_place``: the Pallas stencil wrote the
    region into the destination buffer; ``kernel_put``: the op's array
    was sliced and written back by ``kernel_put``.  ``halo_bytes`` and
    ``keep_bytes``: what the Pallas stencil's grid fetched for its row
    and lane halos and read from the destination, beyond its source
    blocks (``stencil_hd.kernel.SweepPlan.fetches``)."""
    in_place: int = 0
    kernel_put: int = 0
    halo_bytes: int = 0
    keep_bytes: int = 0

    def fetched(self, halo: int, keep: int) -> None:
        self.halo_bytes += halo
        self.keep_bytes += keep


def make_gemm_kernel(a: str = "A", b: str = "B", c: str = "C", *,
                     alpha: float = 1.0, impl: str = "auto",
                     interpret: bool = True):
    """``C[rows, :] = alpha * A[rows, :] @ B`` over the region's row
    band — the row-partitioned GEMM of the paper's Table 3.  ``A`` is
    used with ROW_ALL, ``B`` with COL_ALL (every device reads all of
    B), ``C`` defined with the identity map."""
    from repro.kernels.gemm_hd.ops import gemm

    @device_kernel
    def gemm_hd_kernel(region, bufs):
        rows = region.to_slices()[0]
        out = gemm(bufs[a][rows, :], bufs[b], alpha=alpha, impl=impl,
                   interpret=interpret)
        return {c: kernel_put(bufs[c], (rows, slice(None)), out)}

    return gemm_hd_kernel


def make_jacobi_kernel(src: str = "A", dst: str = "B", *,
                       impl: str = "auto", interpret: bool = True):
    """One Jacobi sweep ``dst[region] = avg4(src)`` over an INTERIOR
    work region (the standard idiom: work partition over
    ``Box.make((1, M-1), (1, N-1))``, boundary rows/cols pass through).
    The halo rows arrive via the planner's ghost-cell exchange.

    With the Pallas stencil and distinct ``src``/``dst`` the op reads
    ``src`` in place and writes the region straight into ``dst``'s
    buffer (``jacobi_step(..., into=)``): no slab, no slice, no
    write-back.  Otherwise (``impl="ref"``, or ``src == dst``, which an
    in-place sweep would read while writing) the op runs on the
    region's row band plus its one-row halo and the result is written
    back by ``kernel_put``.  The kernel's ``paths``
    (:class:`JacobiPaths`) counts which path each call took, and the
    bytes the Pallas stencil fetched beyond its source blocks."""
    from repro.kernels.stencil_hd.kernel import sweep_plan
    from repro.kernels.stencil_hd.ops import jacobi_step, resolve_impl

    paths = JacobiPaths()

    @device_kernel
    def jacobi_hd_kernel(region, bufs):
        (r0, r1), (c0, c1) = region.bounds
        x = bufs[src]
        m, n = x.shape
        pallas = resolve_impl(impl) == "pallas"
        if (src != dst and pallas
                and 1 <= r0 and r1 <= m - 1 and 1 <= c0 and c1 <= n - 1):
            paths.in_place += 1
            paths.fetched(*sweep_plan(x.shape, x.dtype, rows=(r0, r1),
                                      cols=(c0, c1)).fetches())
            return {dst: jacobi_step(x, into=bufs[dst], rows=(r0, r1),
                                     cols=(c0, c1), impl=impl,
                                     interpret=interpret)}
        paths.kernel_put += 1
        assert r0 >= 1 and c0 >= 1 and c1 <= n - 1, (
            "jacobi kernel needs an interior work region")
        # slab = band + vertical halo; the op's edge pass-through rows/
        # cols are exactly the slab rows 0 and -1 (sliced off) and the
        # global cols 0 and n-1 (outside [c0, c1))
        slab = x[r0 - 1:r1 + 1, :]
        if pallas:
            paths.fetched(*sweep_plan(slab.shape, slab.dtype).fetches())
        sw = jacobi_step(slab, impl=impl, interpret=interpret)
        return {dst: kernel_put(bufs[dst],
                                (slice(r0, r1), slice(c0, c1)),
                                sw[1:-1, c0:c1])}

    jacobi_hd_kernel.paths = paths
    return jacobi_hd_kernel


def make_flash_kernel(q: str = "Q", k: str = "K", v: str = "V",
                      o: str = "O", *, heads: int, dim: int,
                      kv_heads: Optional[int] = None,
                      out_dim: Optional[int] = None,
                      window=None, softcap: float = 0.0,
                      scale: Optional[float] = None, impl: str = "auto",
                      block_q: int = 512, block_kv: int = 1024,
                      interpret: bool = True):
    """Causal flash attention over a row band of queries.  The HDArrays
    are 2-D ``(T, heads*dim)`` folded views (one sequence); ``K``/``V``
    are used with ALL_* (every device attends over the full kv range)
    and the region's global row offset becomes the absolute query
    positions, so causality is preserved across the row partition."""
    from repro.kernels.flash_attention.ops import flash_attention

    kv_heads = kv_heads if kv_heads is not None else heads
    out_dim = out_dim if out_dim is not None else dim

    @device_kernel
    def flash_hd_kernel(region, bufs):
        import jax.numpy as jnp

        r0, r1 = region.bounds[0]
        qv = bufs[q][r0:r1, :].reshape(1, r1 - r0, heads, dim)
        kv = bufs[k].reshape(1, -1, kv_heads, dim)
        vv = bufs[v].reshape(1, -1, kv_heads, out_dim)
        qpos = jnp.arange(r0, r1, dtype=jnp.int32)[None, :]
        out = flash_attention(qv, kv, vv, qpos=qpos, window=window,
                              softcap=softcap, scale=scale, impl=impl,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret)
        out = out.reshape(r1 - r0, heads * out_dim)
        return {o: kernel_put(bufs[o], (slice(r0, r1), slice(None)), out)}

    return flash_hd_kernel
