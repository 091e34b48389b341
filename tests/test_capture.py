"""One-program steps and captured steady-state pipelines.

The ambition chain, counter-verified at each link:

* a serial apply_kernel step on the resident jax backend runs exchange
  AND device kernel as ONE jitted program (``PlannerStats.fused_steps``,
  one ``PlannerStats.dispatches`` per step);
* a steady-state pipeline (every step a §4.2 plan hit + commit replay
  for two periods) is captured as ONE jitted ``lax.scan``
  (``scan_captures``), after which the per-step host dispatch count is
  ZERO (``captured_steps`` add no ``dispatches``) — and the results
  stay bit-identical to the unfused Sim oracle, with an identical
  ``comm_log``;
* the real Pallas kernels (interpret mode on CPU) ride inside those
  fused programs via the :mod:`repro.kernels.hd` factories.
"""
import numpy as np
import pytest

from repro.core import AccessSpec, Box, HDArrayRuntime, IDENTITY_2D, ROW_ALL, COL_ALL
from repro.executors import device_kernel, kernel_put

FP = AccessSpec.of((0, 0), (1, 0), (-1, 0), (0, 1), (0, -1))
IDENT = AccessSpec.of((0, 0))


def _need_devices(n):
    import jax
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} host devices (XLA_FLAGS not applied?)")


@device_kernel
def _jac_ab(region, bufs):
    (r0, r1), (c0, c1) = region.bounds
    x = bufs["A"]
    sw = (x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
          + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25
    return {"B": kernel_put(bufs["B"], (slice(r0, r1), slice(c0, c1)), sw)}


@device_kernel
def _jac_ba(region, bufs):
    (r0, r1), (c0, c1) = region.bounds
    x = bufs["B"]
    sw = (x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
          + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25
    return {"A": kernel_put(bufs["A"], (slice(r0, r1), slice(c0, c1)), sw)}


def _jacobi_pipeline(rt, n=48, steps=20, kernels=(_jac_ab, _jac_ba)):
    """Ping-pong Jacobi: the canonical period-2 steady-state pipeline."""
    A, B = rt.create("A", (n, n)), rt.create("B", (n, n))
    pw = rt.partition_row((n, n), region=Box.make((1, n - 1), (1, n - 1)))
    pd = rt.partition_row((n, n))
    init = np.random.default_rng(3).standard_normal((n, n)).astype(np.float32)
    rt.write(A, init, pd)
    rt.write(B, init, pd)
    prog = []
    for i in range(steps):
        if i % 2 == 0:
            prog.append(dict(kernel_name="jab", part_id=pw,
                             kernel=kernels[0], arrays=[A, B],
                             uses={"A": FP}, defs={"B": IDENT}))
        else:
            prog.append(dict(kernel_name="jba", part_id=pw,
                             kernel=kernels[1], arrays=[A, B],
                             uses={"B": FP}, defs={"A": IDENT}))
    rt.run_pipeline(prog)
    outA, outB = rt.read_coherent(A), rt.read_coherent(B)
    return outA, outB, list(rt.comm_log)


def test_fused_steps_counter_and_dispatch_gauge():
    _need_devices(4)
    rt = HDArrayRuntime(4, backend="jax")
    st = rt.planner.stats
    d0 = st.dispatches
    _jacobi_pipeline(rt, steps=4)
    # every serial device-kernel step fused exchange+kernel into ONE
    # program (4 steps run before any capture window can open)
    assert st.fused_steps == 4
    assert st.scan_captures == 0 and st.captured_steps == 0
    # one host dispatch per step
    assert (st.dispatches - d0) / 4 == 1.0
    rt.close()


def test_sim_pipeline_never_captures():
    rt = HDArrayRuntime(4, backend="sim")
    st = rt.planner.stats
    d0 = st.dispatches
    _jacobi_pipeline(rt, steps=12)
    assert st.fused_steps == 0 and st.scan_captures == 0
    assert st.captured_steps == 0
    # unfused step with a kernel: exchange dispatch + kernel dispatch
    assert (st.dispatches - d0) / 12 == 2.0
    rt.close()


def test_overlap_pipeline_two_dispatches_per_step():
    rt = HDArrayRuntime(4, backend="sim", overlap=True)
    st = rt.planner.stats
    d0 = st.dispatches
    _jacobi_pipeline(rt, steps=6)
    # messages on the comm thread, then the kernel: two per step
    assert (st.dispatches - d0) / 6 == 2.0
    assert st.captured_steps == 0
    rt.close()


def test_steady_pipeline_captured_as_scan_zero_dispatches():
    _need_devices(8)
    rt_sim = HDArrayRuntime(8, backend="sim")
    a_sim, b_sim, log_sim = _jacobi_pipeline(rt_sim, steps=20)
    rt_sim.close()

    rt = HDArrayRuntime(8, backend="jax")
    ex = rt.executor
    st = rt.planner.stats
    d0 = st.dispatches
    a_jax, b_jax, log_jax = _jacobi_pipeline(rt, steps=20)

    # the steady state was detected and captured as >= 1 lax.scan ...
    assert st.scan_captures >= 1
    # ... covering every step after the witness window, the last
    # included: the first 10 steps run fused, the other 10 in the scan
    assert st.fused_steps + st.scan_captures < 20
    assert st.fused_steps == 10 and st.captured_steps == 10
    # one host dispatch per fused step, none for the captured ones
    assert st.dispatches - d0 == st.fused_steps
    # scan program cached under a ("scan", ...) signature
    assert any(k and k[0] == "scan" for k in ex._programs)
    # residency held: 2 writes up, 0 down until the reads
    assert ex.h2d_transfers == 2
    assert ex.d2h_transfers == 2

    # bit-identical to the unfused oracle, identical comm_log (the
    # captured steps' plans replay through the same §4.2 metadata)
    assert np.array_equal(a_sim, a_jax)
    assert np.array_equal(b_sim, b_jax)
    assert log_sim == log_jax
    rt.close()


def test_capture_counts_stay_consistent():
    _need_devices(8)
    rt = HDArrayRuntime(8, backend="jax")
    ex = rt.executor
    _jacobi_pipeline(rt, steps=20)
    # every step moved its halo bytes, captured or not — byte/message
    # accounting must match the sim oracle exactly
    rt_sim = HDArrayRuntime(8, backend="sim")
    _jacobi_pipeline(rt_sim, steps=20)
    assert ex.bytes_moved == rt_sim.executor.bytes_moved
    assert ex.messages_executed == rt_sim.executor.messages_executed
    # one device kernel per step, scanned or fused
    assert ex.device_kernel_launches == 20
    rt.close()
    rt_sim.close()


def test_host_kernel_pipeline_falls_back_unfused():
    _need_devices(4)

    def host_jac(region, bufs):            # unmarked: host mirrors
        (r0, r1), (c0, c1) = region.bounds
        x = bufs["A"]
        sw = (x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
              + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25
        bufs["B"][r0:r1, c0:c1] = sw

    def host_jac_back(region, bufs):
        (r0, r1), (c0, c1) = region.bounds
        x = bufs["B"]
        sw = (x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
              + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25
        bufs["A"][r0:r1, c0:c1] = sw

    rt_sim = HDArrayRuntime(4, backend="sim")
    a_s, b_s, _ = _jacobi_pipeline(rt_sim, steps=10,
                                   kernels=(host_jac, host_jac_back))
    rt_sim.close()
    rt = HDArrayRuntime(4, backend="jax")
    a_j, b_j, _ = _jacobi_pipeline(rt, steps=10,
                                   kernels=(host_jac, host_jac_back))
    st = rt.planner.stats
    assert st.fused_steps == 0 and st.scan_captures == 0
    assert np.array_equal(a_s, a_j) and np.array_equal(b_s, b_j)
    rt.close()


# -- the real Pallas kernels inside fused programs ----------------------
def _gemm_program(rt, kernel, n=32, steps=8):
    A, B, C = (rt.create(nm, (n, n)) for nm in ("A", "B", "C"))
    part = rt.partition_row((n, n))
    rng = np.random.default_rng(5)
    rt.write(A, rng.standard_normal((n, n)).astype(np.float32), part)
    rt.write_replicated(B, rng.standard_normal((n, n)).astype(np.float32))
    rt.write(C, np.zeros((n, n), np.float32), part)
    prog = [dict(kernel_name="gemm", part_id=part, kernel=kernel,
                 arrays=[A, B, C],
                 uses={"A": ROW_ALL, "B": COL_ALL},
                 defs={"C": IDENTITY_2D})
            for _ in range(steps)]
    rt.run_pipeline(prog)
    return rt.read_coherent(C)


def test_hd_gemm_pallas_kernel_fused_and_captured():
    _need_devices(8)
    from repro.kernels.hd import make_gemm_kernel

    kern = make_gemm_kernel(impl="pallas")
    rt_sim = HDArrayRuntime(8, backend="sim")
    c_sim = _gemm_program(rt_sim, kern)
    rt_sim.close()

    rt = HDArrayRuntime(8, backend="jax")
    st = rt.planner.stats
    d0 = st.dispatches
    c_jax = _gemm_program(rt, kern)
    # period-1 steady state: captured after the witness, up to the last
    # of the 8 steps: 4 fused, then 4 inside the scan
    assert st.scan_captures >= 1
    assert st.fused_steps == 4 and st.captured_steps == 4
    # one host dispatch per fused step, none for the captured ones
    assert st.dispatches - d0 == st.fused_steps
    # one kernel source, bit-identical across backends (both run the
    # same jitted interpret-mode Pallas program on this host)
    assert np.array_equal(c_sim, c_jax)
    rt.close()


def test_hd_jacobi_pallas_kernel_bit_identical_across_backends():
    _need_devices(8)
    from repro.kernels.hd import make_jacobi_kernel

    ab = make_jacobi_kernel("A", "B", impl="pallas")
    ba = make_jacobi_kernel("B", "A", impl="pallas")
    rt_sim = HDArrayRuntime(8, backend="sim")
    a_s, b_s, log_s = _jacobi_pipeline(rt_sim, steps=12, kernels=(ab, ba))
    rt_sim.close()
    rt = HDArrayRuntime(8, backend="jax")
    a_j, b_j, log_j = _jacobi_pipeline(rt, steps=12, kernels=(ab, ba))
    assert rt.planner.stats.scan_captures >= 1
    assert np.array_equal(a_s, a_j) and np.array_equal(b_s, b_j)
    assert log_s == log_j
    rt.close()


def test_null_backend_pipeline_metadata_parity():
    # metadata-only: plans (and the §4.2 cache) must behave exactly as
    # the data backends, with no capture engaging (kernel=None steps)
    rt = HDArrayRuntime(8, backend="null")
    A = rt.create("A", (32, 32))
    B = rt.create("B", (32, 32))
    pw = rt.partition_row((32, 32), region=Box.make((1, 31), (1, 31)))
    prog = []
    for i in range(10):
        if i % 2 == 0:
            prog.append(dict(kernel_name="jab", part_id=pw, kernel=None,
                             arrays=[A, B], uses={"A": FP},
                             defs={"B": IDENT}))
        else:
            prog.append(dict(kernel_name="jba", part_id=pw, kernel=None,
                             arrays=[A, B], uses={"B": FP},
                             defs={"A": IDENT}))
    plans = rt.run_pipeline(prog)
    assert len(plans) == 10 and all(p is not None for p in plans)
    assert rt.planner.stats.scan_captures == 0
    rt.close()


# -- the accelerator program shapes, run on the CPU ----------------------
# On the XLA cpu host platform the jax executor stages multi-collective
# plans one collective per dispatch and runs kernel-only steps per
# shard.  Elsewhere a plan or a step is ONE shard_map program and the
# kernel sweep is a lax.switch over ranks.  Patching the executor's
# one predicate runs those accelerator shapes here, against sim.
@pytest.fixture
def accelerator_shapes(monkeypatch):
    from repro.executors import jax_exec

    monkeypatch.setattr(jax_exec, "cpu_host_platform", lambda: False)


def _program_modes(ex):
    """Which program shapes the executor built, by cache-key kind."""
    modes = set()
    for key, prog in ex._programs.items():
        if key[0] == "step":
            modes.add(prog[0])                 # "fused" | "staged"
        elif isinstance(key[0], str):
            modes.add(key[0])                  # kernel | kernelps | scan
        else:                                  # plan program: stage list
            modes.add("plan" if prog[0][0][0] is None else "plan_staged")
    return modes


def test_accelerator_fused_halo_steps_bit_identical_to_sim(
        accelerator_shapes):
    _need_devices(4)
    rt_sim = HDArrayRuntime(4, backend="sim")
    a_s, b_s, log_s = _jacobi_pipeline(rt_sim, steps=12)
    rt_sim.close()
    rt = HDArrayRuntime(4, backend="jax")
    a_j, b_j, log_j = _jacobi_pipeline(rt, steps=12)
    # both halo ppermutes and the lax.switch sweep in ONE step program,
    # then the captured scan
    assert _program_modes(rt.executor) == {"fused", "scan"}
    assert rt.executor.collective_counts["ppermute"] > 0
    assert rt.planner.stats.scan_captures >= 1
    assert np.array_equal(a_s, a_j) and np.array_equal(b_s, b_j)
    assert log_s == log_j
    rt.close()


@device_kernel
def _gemm_jnp(region, bufs):
    import jax.numpy as jnp

    rows = region.to_slices()[0]
    out = jnp.dot(bufs["A"][rows, :], bufs["B"])
    return {"C": kernel_put(bufs["C"], (rows, slice(None)), out)}


def test_accelerator_gemm_gather_then_kernel_only_steps(accelerator_shapes):
    _need_devices(4)
    n = 32
    rng = np.random.default_rng(9)
    # small integers: every product and sum is exact in float32, so
    # the two backends' dots agree bit for bit
    a = rng.integers(-4, 5, (n, n)).astype(np.float32)
    b = rng.integers(-4, 5, (n, n)).astype(np.float32)
    outs = {}
    for backend in ("sim", "jax"):
        rt = HDArrayRuntime(4, backend=backend)
        A, B, C = (rt.create(nm, (n, n)) for nm in ("A", "B", "C"))
        part = rt.partition_row((n, n))
        rt.write(A, a, part)
        rt.write(B, b, part)           # row-partitioned: B is gathered
        rt.write(C, np.zeros((n, n), np.float32), part)
        rt.run_pipeline([dict(kernel_name="gemm", part_id=part,
                              kernel=_gemm_jnp, arrays=[A, B, C],
                              uses={"A": ROW_ALL, "B": COL_ALL},
                              defs={"C": IDENTITY_2D})] * 6)
        outs[backend] = rt.read_coherent(C)
        if backend == "jax":
            ex = rt.executor
            assert ex.collective_counts["all_gather"] >= 1
            # the gather step and the traffic-free steps are each ONE
            # program (no per-shard kernel dispatch)
            assert "kernelps" not in _program_modes(ex)
            assert "fused" in _program_modes(ex)
        rt.close()
    assert np.array_equal(outs["sim"], a @ b)
    assert np.array_equal(outs["jax"], outs["sim"])


def test_accelerator_overlap_and_host_kernel_paths(accelerator_shapes):
    _need_devices(4)

    def host_jac(region, bufs):            # unmarked: host mirrors
        (r0, r1), (c0, c1) = region.bounds
        x = bufs["A"]
        bufs["B"][r0:r1, c0:c1] = (
            x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
            + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25

    def host_jac_back(region, bufs):
        (r0, r1), (c0, c1) = region.bounds
        x = bufs["B"]
        bufs["A"][r0:r1, c0:c1] = (
            x[r0:r1, c0 - 1:c1 - 1] + x[r0:r1, c0 + 1:c1 + 1]
            + x[r0 - 1:r1 - 1, c0:c1] + x[r0 + 1:r1 + 1, c0:c1]) * 0.25

    rt_sim = HDArrayRuntime(4, backend="sim")
    ref = _jacobi_pipeline(rt_sim, steps=8)[:2]
    rt_sim.close()
    # overlap schedule: messages as one fused plan program, device
    # kernels as the lax.switch sweep
    rt = HDArrayRuntime(4, backend="jax", overlap=True)
    got = _jacobi_pipeline(rt, steps=8)[:2]
    assert {"plan", "kernel"} <= _program_modes(rt.executor)
    assert "plan_staged" not in _program_modes(rt.executor)
    assert all(np.array_equal(r, g) for r, g in zip(ref, got))
    rt.close()
    # host kernels: the exchange is one fused plan program
    rt = HDArrayRuntime(4, backend="jax")
    got = _jacobi_pipeline(rt, steps=8,
                           kernels=(host_jac, host_jac_back))[:2]
    assert _program_modes(rt.executor) == {"plan"}
    assert all(np.array_equal(r, g) for r, g in zip(ref, got))
    rt.close()
