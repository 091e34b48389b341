"""Device-residency + one-program-step study: the resident JaxExecutor
(fused steps, captured pipelines) vs the pre-PR stack/put/get round
trip.

The pre-residency ``jax`` backend staged every step through the host:
``np.stack`` the mirrors, one ``device_put``, the collective program,
one ``device_get``, section copy-back — and ran kernels on host numpy.
The resident executor keeps shards on the mesh across steps, fuses each
WHOLE step (exchange + device kernel) into one jitted shard_map program
(``Executor.execute_step``), and captures a steady-state pipeline as
ONE jitted ``lax.scan`` (``Executor.capture_cycle``) — so K steady
steps cost a single Python dispatch and zero host↔device traffic.

This benchmark runs the same multi-step programs (Jacobi pipeline and a
GEMM step loop, P >= 8) four ways —

  * ``sim``          — the numpy oracle (parity reference),
  * ``jax legacy``   — ``JaxExecutor(resident=False)``: the pre-PR
                       per-step round trip, same collectives,
  * ``jax resident`` — device-resident, one fused program per step,
  * ``jax captured`` — ``run_pipeline``: the steady state runs inside a
                       captured ``lax.scan`` —

and reports per-step wall clock, the full-buffer transfer counters
(``h2d_transfers`` / ``d2h_transfers``), the one-program counters
(``fused_steps`` / ``scan_captures`` /
``python_dispatches_per_step``) and a roofline-fraction line for the
captured program (achieved useful FLOPs vs the architecture peak, via
``src/repro/roofline``).  It FAILS loudly unless

  * legacy is bit-identical to sim, captured is bit-identical to
    resident (same traced step programs), and resident matches sim
    (bit-identical for Jacobi; float32-dot tolerance for GEMM, whose
    sim kernel is numpy BLAS),
  * the resident/captured steady state moved zero full buffers,
  * the captured pipeline reaches python_dispatches_per_step == 0,
  * (full mode) the resident Jacobi pipeline is >= 5x faster per
    steady step than legacy (its legacy cost is transfer-dominated),
  * (full mode) the captured GEMM loop is >= 1.3x faster per step than
    legacy (run at n=256, the dispatch-bound regime the scan capture
    exists for — at large n both sides sit on the same BLAS roofline).

Quick mode (CI) checks parity + zero steady transfers + the zero-
dispatch capture only: per-step times on small arrays are noise.

Run:  PYTHONPATH=src python -m benchmarks.executor_residency [--quick]
      python -m benchmarks.run residency        # quick smoke (CI)

Full mode writes results/executor_residency.json + BENCH_executor.json
(quick mode writes results/executor_residency_quick.json only).
"""
from __future__ import annotations

import json
import sys
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

SPEEDUP_FLOOR = 5.0          # jacobi: resident >= 5x legacy per step
GEMM_SPEEDUP_FLOOR = 1.3     # gemm: captured >= 1.3x legacy per step
PARITY_STEPS = 12            # fixed-length parity programs


def _set_flags():
    from repro.launch.mesh import ensure_host_devices
    ensure_host_devices(8)


# -- programs (device-kernel convention: one source, every backend) ----
def _jacobi(rt, n) -> Tuple[Callable[[int], Dict], Callable]:
    """Ping-pong Jacobi (A and B swap roles each sweep) — every step is
    one halo exchange plus one stencil sweep, the §4.2 steady state
    with period 2."""
    from repro.core import AccessSpec, Box, IDENTITY_2D
    from repro.executors import device_kernel, kernel_put

    rng = np.random.default_rng(11)
    B0 = rng.normal(size=(n, n)).astype(np.float32)
    fp = AccessSpec.of((0, -1), (0, 1), (-1, 0), (1, 0), (0, 0))
    pd = rt.partition_row((n, n))
    pw = rt.partition_row((n, n), region=Box.make((1, n - 1), (1, n - 1)))
    hA, hB = rt.create("A", (n, n)), rt.create("B", (n, n))
    rt.write(hA, B0, pd)
    rt.write(hB, B0, pd)

    def sweep(src, dst):
        @device_kernel
        def jac(region, bufs):
            (r0, r1), (c0, c1) = region.bounds
            Sv = bufs[src]
            new = (Sv[r0:r1, c0 - 1:c1 - 1] + Sv[r0:r1, c0 + 1:c1 + 1]
                   + Sv[r0 - 1:r1 - 1, c0:c1] + Sv[r0 + 1:r1 + 1, c0:c1]) / 4
            return {dst: kernel_put(bufs[dst],
                                    (slice(r0, r1), slice(c0, c1)), new)}
        return jac

    jac_ab = sweep("B", "A")
    jac_ba = sweep("A", "B")

    def step_dict(i: int) -> Dict:
        if i % 2 == 0:
            return dict(kernel_name="jac_ab", part_id=pw, kernel=jac_ab,
                        arrays=[hA, hB], uses={"B": fp},
                        defs={"A": IDENTITY_2D})
        return dict(kernel_name="jac_ba", part_id=pw, kernel=jac_ba,
                    arrays=[hA, hB], uses={"A": fp},
                    defs={"B": IDENTITY_2D})

    return step_dict, (lambda: rt.read_coherent(hB))


def _gemm(rt, n) -> Tuple[Callable[[int], Dict], Callable]:
    """Row-band GEMM through the REAL kernel op (``repro.kernels.hd``
    factory -> ``gemm_hd``; jitted jnp on CPU hosts, Pallas on TPU).
    One kernel source for every mode: legacy pays the per-call
    host->device->host staging of the jitted op, resident/captured run
    it inside the fused step / scanned programs."""
    from repro.core import COL_ALL, IDENTITY_2D, ROW_ALL
    from repro.kernels.hd import make_gemm_kernel

    rng = np.random.default_rng(12)
    A = rng.normal(size=(n, n)).astype(np.float32)
    B = rng.normal(size=(n, n)).astype(np.float32)
    part = rt.partition_row((n, n))
    hA, hB, hC = (rt.create(s, (n, n)) for s in "abc")
    rt.write(hA, A, part)
    rt.write(hB, B, part)
    rt.write(hC, np.zeros((n, n), np.float32), part)
    mm = make_gemm_kernel("a", "b", "c")

    def step_dict(i: int) -> Dict:
        return dict(kernel_name="gemm", part_id=part, kernel=mm,
                    arrays=[hA, hB, hC],
                    uses={"a": ROW_ALL, "b": COL_ALL},
                    defs={"c": IDENTITY_2D})

    return step_dict, (lambda: rt.read(hC, part))


PROGRAMS = {"jacobi": _jacobi, "gemm": _gemm}
# useful FLOPs per step (the roofline numerator): GEMM 2n^3, Jacobi
# 4 flops per interior point
MODEL_FLOPS = {"gemm": lambda n: 2.0 * n ** 3,
               "jacobi": lambda n: 4.0 * (n - 2) ** 2}


def _make_rt(mode: str, nproc: int):
    from repro.core import HDArrayRuntime
    from repro.executors import JaxExecutor

    if mode == "sim":
        return HDArrayRuntime(nproc, backend="sim")
    return HDArrayRuntime(nproc, backend="jax", executor=JaxExecutor(
        nproc, resident=(mode != "jax legacy")))


def _apply(rt, st: Dict) -> None:
    rt.apply_kernel(st["kernel_name"], st["part_id"], st["kernel"],
                    st["arrays"], st["uses"], st["defs"],
                    **st.get("kw", {}))


def _run_serial(program: str, mode: str, nproc: int, n: int, iters: int,
                warmup: int) -> Tuple[Dict, np.ndarray]:
    rt = _make_rt(mode, nproc)
    step_dict, finish = PROGRAMS[program](rt, n)
    k = 0
    for _ in range(warmup):                    # cold: compile + upload
        _apply(rt, step_dict(k)); k += 1
    ex = rt.executor
    h2d0 = getattr(ex, "h2d_transfers", 0)
    d2h0 = getattr(ex, "d2h_transfers", 0)
    t0 = time.perf_counter()
    for _ in range(iters):
        _apply(rt, step_dict(k)); k += 1
    per_step = (time.perf_counter() - t0) / iters
    st = rt.planner.stats
    row = {
        "program": program, "mode": mode, "nproc": nproc, "n": n,
        "iters": iters, "per_step_s": per_step,
        "steady_h2d": getattr(ex, "h2d_transfers", 0) - h2d0,
        "steady_d2h": getattr(ex, "d2h_transfers", 0) - d2h0,
        "bytes_moved": ex.bytes_moved,
        "fused_steps": st.fused_steps, "scan_captures": st.scan_captures,
        "dispatches_per_step": st.python_dispatches_per_step,
    }
    if mode != "sim":
        row["collectives"] = dict(ex.collective_counts)
    return row, finish()


def _run_captured(program: str, nproc: int, n: int, iters: int,
                  timed_pipelines: int = 3) -> Tuple[Dict, np.ndarray, Dict]:
    """The ``run_pipeline`` path: the steady state is captured as one
    jitted lax.scan.  Warmup runs the pipeline twice (the cold run and
    the warm run compile scans of different lengths — detection starts
    earlier once every plan is §4.2-cached); the timed pipelines then
    replay cached programs only."""
    rt = _make_rt("jax captured", nproc)
    step_dict, finish = PROGRAMS[program](rt, n)
    steps = [step_dict(i) for i in range(iters)]
    for _ in range(2):
        rt.run_pipeline(steps)
    ex = rt.executor
    h2d0, d2h0 = ex.h2d_transfers, ex.d2h_transfers
    t0 = time.perf_counter()
    for _ in range(timed_pipelines):
        rt.run_pipeline(steps)
    per_step = (time.perf_counter() - t0) / (timed_pipelines * iters)
    st = rt.planner.stats
    row = {
        "program": program, "mode": "jax captured", "nproc": nproc, "n": n,
        "iters": iters, "per_step_s": per_step,
        "steady_h2d": ex.h2d_transfers - h2d0,
        "steady_d2h": ex.d2h_transfers - d2h0,
        "bytes_moved": ex.bytes_moved,
        "fused_steps": st.fused_steps, "scan_captures": st.scan_captures,
        "dispatches_per_step": st.python_dispatches_per_step,
        "collectives": dict(ex.collective_counts),
    }
    roof = _roofline_row(program, ex, n, nproc)
    return row, finish(), roof


def _roofline_row(program: str, ex, n: int, nproc: int) -> Dict:
    """Achieved-vs-peak report for the captured program: lower+compile
    the scan from its stored avals and walk the HLO cost model."""
    compiled, meta = ex.last_program_lowered()
    steps_covered = meta.get("reps", 1) * meta.get("steps", 1)
    try:
        from repro.roofline.analysis import analyze
        rep = analyze(compiled, arch="tpu-peak-ref",
                      shape=f"{program}-n{n}", mesh_name=f"host{nproc}",
                      n_chips=nproc,
                      model_flops_total=MODEL_FLOPS[program](n)
                      * steps_covered)
    except Exception as e:              # roofline is reporting, not a gate
        return {"error": repr(e)}
    return {"program": program, "kind": meta.get("kind"),
            "steps_in_program": steps_covered,
            "hlo_flops_per_device": rep.hlo_flops,
            "useful_ratio": rep.useful_ratio,
            "bottleneck": rep.bottleneck,
            "roofline_fraction": rep.roofline_fraction}


def _parity(program: str, nproc: int, n: int) -> Dict[str, int]:
    """Fixed-length programs, every mode, outputs compared:
    legacy == sim bit-for-bit, captured == resident bit-for-bit (same
    traced step tracers, scan vs unfused), resident vs sim exact for
    Jacobi / float32-dot tolerance for GEMM."""
    outs = {}
    stats = {}
    for mode in ("sim", "jax legacy", "jax resident"):
        rt = _make_rt(mode, nproc)
        step_dict, finish = PROGRAMS[program](rt, n)
        for i in range(PARITY_STEPS):
            _apply(rt, step_dict(i))
        outs[mode] = finish()
    rt = _make_rt("jax captured", nproc)
    step_dict, finish = PROGRAMS[program](rt, n)
    rt.run_pipeline([step_dict(i) for i in range(PARITY_STEPS)])
    outs["jax captured"] = finish()
    st = rt.planner.stats
    stats["scan_captures"] = st.scan_captures
    stats["dispatches_per_step"] = st.python_dispatches_per_step

    if not np.array_equal(outs["sim"], outs["jax legacy"]):
        raise SystemExit(f"PARITY FAILURE: sim != jax legacy ({program})")
    if not np.array_equal(outs["jax resident"], outs["jax captured"]):
        raise SystemExit(f"PARITY FAILURE: resident != captured "
                         f"({program}) — the scan is not bit-identical "
                         "to the unfused path")
    exact = np.array_equal(outs["sim"], outs["jax resident"])
    if program == "jacobi" and not exact:
        raise SystemExit("PARITY FAILURE: sim != jax resident (jacobi)")
    if not exact and not np.allclose(outs["sim"], outs["jax resident"],
                                     rtol=2e-5, atol=1e-4):
        raise SystemExit(f"PARITY FAILURE: sim !~ jax resident ({program})")
    if stats["scan_captures"] < 1:
        raise SystemExit(f"CAPTURE FAILURE: {program} pipeline never "
                         "captured a steady-state scan")
    if stats["dispatches_per_step"] != 0.0:
        raise SystemExit(f"CAPTURE FAILURE: {program} captured pipeline "
                         f"ended at {stats['dispatches_per_step']} host "
                         "dispatches per step (expected 0)")
    return stats


def main(quick: bool = False) -> dict:
    _set_flags()
    import jax

    nproc = 8
    if len(jax.devices()) < nproc:
        raise SystemExit(f"executor_residency: needs {nproc} host devices, "
                         f"found {len(jax.devices())} (jax initialized "
                         "before ensure_host_devices?)")
    # iters must leave >= one full period after the two-period capture
    # witness (detection at i = 2*d, d = 2 for the jacobi ping-pong);
    # warmup must cover two periods — the planner's cold first period
    # produces different step-program cache keys than the steady one,
    # so a shorter warmup leaks those compiles into the timed loop
    iters, warmup = (8, 4) if quick else (12, 4)
    # jacobi at transfer-dominated size; gemm at the dispatch-bound size
    # the scan-capture gate targets (see module docstring)
    sizes = {"jacobi": 128, "gemm": 128} if quick \
        else {"jacobi": 1024, "gemm": 256}
    rows: List[Dict] = []
    rooflines: Dict[str, dict] = {}
    summary: Dict[str, dict] = {}
    print(f"{'program':8s} {'mode':14s} {'ms/step':>9s} {'steady h2d':>10s} "
          f"{'steady d2h':>10s} {'disp/step':>9s}")
    for program in PROGRAMS:
        n = sizes[program]
        cap_stats = _parity(program, nproc, min(n, 128))
        for mode in ("sim", "jax legacy", "jax resident"):
            row, _out = _run_serial(program, mode, nproc, n, iters, warmup)
            rows.append(row)
            print(f"{program:8s} {mode:14s} {row['per_step_s']*1e3:9.3f} "
                  f"{row['steady_h2d']:10d} {row['steady_d2h']:10d} "
                  f"{row['dispatches_per_step']:9.1f}")
        crow, _out, roof = _run_captured(program, nproc, n, iters)
        rows.append(crow)
        rooflines[program] = roof
        print(f"{program:8s} {'jax captured':14s} "
              f"{crow['per_step_s']*1e3:9.3f} {crow['steady_h2d']:10d} "
              f"{crow['steady_d2h']:10d} {crow['dispatches_per_step']:9.1f}")
        by_mode = {r["mode"]: r for r in rows if r["program"] == program}
        legacy, res, cap = (by_mode["jax legacy"], by_mode["jax resident"],
                            by_mode["jax captured"])
        speedup = legacy["per_step_s"] / res["per_step_s"]
        cap_speedup = legacy["per_step_s"] / cap["per_step_s"]
        summary[program] = {
            "nproc": nproc, "n": n, "iters": iters,
            "legacy_per_step_s": legacy["per_step_s"],
            "resident_per_step_s": res["per_step_s"],
            "captured_per_step_s": cap["per_step_s"],
            "speedup": speedup,
            "captured_speedup": cap_speedup,
            "legacy_steady_h2d": legacy["steady_h2d"],
            "legacy_steady_d2h": legacy["steady_d2h"],
            "resident_steady_h2d": res["steady_h2d"],
            "resident_steady_d2h": res["steady_d2h"],
            "captured_steady_h2d": cap["steady_h2d"],
            "captured_steady_d2h": cap["steady_d2h"],
            "captured_dispatches_per_step": cap["dispatches_per_step"],
            "scan_captures": cap["scan_captures"],
            "roofline_fraction": rooflines[program].get(
                "roofline_fraction"),
            "parity": True, **{f"parity_{k}": v for k, v in
                               cap_stats.items()},
        }
        print(f"{'':8s} parity ✓   resident {speedup:5.1f}x   captured "
              f"{cap_speedup:5.1f}x vs legacy   roofline_fraction "
              f"{rooflines[program].get('roofline_fraction', 0) or 0:.2e}")
        for r in (res, cap):
            if r["steady_h2d"] or r["steady_d2h"]:
                raise SystemExit(
                    f"RESIDENCY FAILURE: {program} {r['mode']} moved "
                    f"{r['steady_h2d']}+{r['steady_d2h']} full buffers in "
                    "steady state (expected zero)")
        if cap["dispatches_per_step"] != 0.0:
            raise SystemExit(f"CAPTURE FAILURE: {program} timed pipeline "
                             "did not end inside a captured scan")
    out = {"quick": quick, "summary": summary, "rooflines": rooflines}
    import os
    os.makedirs("results", exist_ok=True)
    dest = ("results/executor_residency_quick.json" if quick
            else "results/executor_residency.json")
    with open(dest, "w") as f:
        json.dump({"rows": rows, **out}, f, indent=1)
    if not quick:
        with open("BENCH_executor.json", "w") as f:
            json.dump(out, f, indent=1)
    print(f"# -> {dest}" + ("" if quick else " + BENCH_executor.json"))
    if not quick:
        jac = summary["jacobi"]["speedup"]
        if jac < SPEEDUP_FLOOR:
            raise SystemExit(f"executor_residency: speedup regression — "
                             f"jacobi {jac:.1f}x < {SPEEDUP_FLOOR}x per "
                             "steady step")
        gem = summary["gemm"]["captured_speedup"]
        if gem < GEMM_SPEEDUP_FLOOR:
            raise SystemExit(f"executor_residency: speedup regression — "
                             f"gemm captured {gem:.2f}x < "
                             f"{GEMM_SPEEDUP_FLOOR}x vs legacy per step")
        print(f"# jacobi resident {jac:.1f}x (floor {SPEEDUP_FLOOR}x); "
              f"gemm captured {gem:.2f}x (floor {GEMM_SPEEDUP_FLOOR}x); "
              "zero steady transfers; 0 dispatches/step captured; parity "
              "OK")
    else:
        print("# quick mode: parity + zero steady transfers + zero-"
              "dispatch capture verified")
    return out


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
