"""Driver ``hdarray_pipeline``: an HDArray program called back to back.

The configuration gives the grid and the devices; the traffic gives
the sweeps per call and how far ahead calls are dispatched.  Set-up
writes the grid from the seed (the runtime's ``write`` takes host
arrays), builds ping-pong Jacobi over a row partition with the Pallas
stencil on ``HDArrayRuntime(p, backend="jax")`` and makes warm-up
calls, which compile every step program, the captured scan and the
call's marker.  The window then calls ``run_pipeline`` until
``seconds`` have passed, keeping about ``ahead_s`` seconds of calls
dispatched ahead of the one it waits for, so that the chip stays fed
while the host stands still.  When the time is up it sends nothing
more, waits for every call sent and reads the clock after that wait:
``step_ms`` is that time over the sweeps of all the calls made.

``correct`` compares both arrays after the last call with the plain
reference (``refs/jacobi.py``) run from the same grid for the same
number of sweeps: every element must be equal.
"""
from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

from chipbench import harness
from chipbench.refs import jacobi as ref


def make_grid(config: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.random((config["grid_rows"], config["grid_cols"]),
                      dtype=np.float32)


def build(config: dict, traffic: dict, x0: np.ndarray, *, interpret=False):
    """The runtime, its two arrays and one call's steps."""
    from repro.core import AccessSpec, Box, HDArrayRuntime, IDENTITY_2D
    from repro.kernels.hd import make_jacobi_kernel

    m, n = x0.shape
    rt = HDArrayRuntime(config["devices"], backend="jax")
    data = rt.partition_row((m, n))
    work = rt.partition_row((m, n), region=Box.make((1, m - 1), (1, n - 1)))
    ha, hb = rt.create("A", (m, n)), rt.create("B", (m, n))
    rt.write(ha, x0, data)
    rt.write(hb, x0, data)
    four = AccessSpec.of((0, -1), (0, 1), (-1, 0), (1, 0), (0, 0))
    k_ab = make_jacobi_kernel("B", "A", impl="pallas", interpret=interpret)
    k_ba = make_jacobi_kernel("A", "B", impl="pallas", interpret=interpret)
    ident = IDENTITY_2D
    steps = [dict(kernel_name="jac_ab", part_id=work, kernel=k_ab,
                  arrays=[ha, hb], uses={"B": four}, defs={"A": ident})
             if i % 2 == 0 else
             dict(kernel_name="jac_ba", part_id=work, kernel=k_ba,
                  arrays=[ha, hb], uses={"A": four}, defs={"B": ident})
             for i in range(traffic["sweeps_per_call"])]
    return rt, ha, hb, steps


def _first_element(x):
    return x[(0,) * x.ndim]


_MARK = None


def call(rt, steps):
    """Dispatch one call and return a one-element marker, ready once the
    call's last program has run on the first chip.  The marker reads the
    largest live array, a grid that program wrote, on its first shard
    only, so it needs no exchange between chips."""
    import jax

    global _MARK
    if _MARK is None:
        _MARK = jax.jit(_first_element)
    rt.run_pipeline(steps)
    newest = max(jax.live_arrays(), key=lambda a: a.nbytes)
    return _MARK(newest.addressable_shards[0].data)


def drain() -> None:
    import jax

    jax.block_until_ready(jax.live_arrays())


def run(cell: harness.Cell, *, seed: int, seconds: float, tracing: bool,
        devices, t_start: float, interpret: bool = False) -> harness.Run:
    from chipbench import trace

    config, traffic = cell.config, cell.traffic
    sweeps = traffic["sweeps_per_call"]
    if sweeps % 2:
        raise ValueError("sweeps_per_call must be even: the result is B")
    spans = harness.Spans(tracing)
    counter = harness.CompileCounter()
    x0 = make_grid(config, seed)
    rt, ha, hb, steps = build(config, traffic, x0, interpret=interpret)
    del x0
    for _ in range(traffic["warm_calls"]):
        t0 = time.perf_counter()
        call(rt, steps).block_until_ready()
        drain()
        last = time.perf_counter() - t0
    calls = traffic["warm_calls"]
    # calls in flight beyond the one waited for: about ahead_s seconds
    ahead = max(1, math.ceil(traffic["ahead_s"] / last))
    st = rt.planner.stats
    ex = rt.executor
    before = (st.scan_captures, st.fused_steps, ex.h2d_transfers,
              ex.d2h_transfers)

    counter.counting = True
    with trace.recording(tracing) as found:
        with spans(trace.WINDOW):
            w0 = time.perf_counter()
            setup_s = w0 - t_start
            made = 0
            pending = collections.deque()
            while time.perf_counter() - w0 < seconds:
                with spans("call"):
                    pending.append(call(rt, steps))
                made += 1
                while len(pending) > ahead:
                    with spans("wait"):
                        pending.popleft().block_until_ready()
            with spans("drain"):
                drain()
            window = time.perf_counter() - w0
    counter.counting = False
    calls += made
    harness.log(f"window: {made} calls of {sweeps} sweeps in {window:.3f} s, "
                f"{ahead} ahead (last warm call {last:.3f} s); "
                f"programs lowered {counter.lowered}, compiled "
                f"{counter.compiled}; in the window scan_captures "
                f"+{st.scan_captures - before[0]}, fused_steps "
                f"+{st.fused_steps - before[1]}, h2d "
                f"+{ex.h2d_transfers - before[2]}, d2h "
                f"+{ex.d2h_transfers - before[3]}, collectives "
                f"{ {k: v for k, v in ex.collective_counts.items() if v} }")
    reduced = None
    if tracing:
        reduced = trace.reduce(found[0])
        trace.discard(found)
    memory = harness.memory_peak(devices)

    # the program's answer, then the reference from the same grid
    got_a = rt.read_coherent(ha)
    got_b = rt.read_coherent(hb)
    rt.close()
    del rt, ha, hb, steps, pending
    gc.collect()
    total = calls * sweeps
    t_ref = time.perf_counter()
    want_a, want_b = reference(config, seed, total, devices)
    harness.log(f"reference: {total} sweeps in "
                f"{time.perf_counter() - t_ref:.3f} s")
    checks = [("A_elements_differing", int(np.sum(got_a != want_a)), 0),
              ("B_elements_differing", int(np.sum(got_b != want_b)), 0)]
    harness.log(f"max |B - reference| "
                f"{float(np.max(np.abs(got_b - want_b))):.6g}")
    step_ms = window / (made * sweeps) * 1e3
    return harness.Run(
        attempted=made, failed=0,
        end_to_end={"step_ms": step_ms, "setup_s": setup_s},
        checks=checks, correct=harness.within(checks),
        spans=spans, devices=devices, trace=reduced,
        memory_peak_bytes=memory,
        facts={"sweeps_per_call": sweeps, "calls": made, "window_s": window,
               "chips": len(devices), "calls_ahead": ahead})


def reference(config: dict, seed: int, total: int, devices):
    """The grid after ``total - 1`` and after ``total`` sweeps (the
    program's A and B), on host."""
    import jax.numpy as jnp

    f, place = ref.sweeps_fn(devices)
    x = f(place(make_grid(config, seed)), jnp.int32(total - 1))
    a = np.asarray(x)
    b = np.asarray(f(x, jnp.int32(1)))
    return a, b
