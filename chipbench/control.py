"""Readings that a cell's limits are set from: the program's compared
numbers and the control's, on several seeds in one process.

    python3 chipbench/control.py --workload jacobi.4chip --seeds 1,2,3 \
        --seconds 3

The control is the plain reference computed one precision below what
the configuration states (its ``check.control``), in the program's
place.  For a serving cell each seed is a run of the driver with a
short window at the cell's own load; the sample it checks also goes
through the control, which reads at each position the reference gap
of the token that the lower precision puts first.  For a Jacobi cell
each seed is a run of the driver, and the control is the reference in
the lower precision against float32 after the same sweeps.  The
benchmark's runs never call this; the readings and the limits set from
them are in ``PERF.md``.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.run import use_compile_cache  # noqa: E402


def jacobi_control(config: dict, seed: int, sweeps: int, devices,
                   low: str) -> int:
    """Elements of the reference run in ``low`` that differ from the
    float32 reference after ``sweeps`` sweeps."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.drivers.hdarray_pipeline import make_grid
    from chipbench.refs import jacobi as ref

    f, place = ref.sweeps_fn(devices)
    x0 = make_grid(config, seed)
    want = np.asarray(f(place(x0), jnp.int32(sweeps)))
    got = f(place(x0.astype(jnp.dtype(low))), jnp.int32(sweeps))
    return int(np.sum(np.asarray(got).astype(np.float32) != want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    use_compile_cache()
    devices = harness.require_chips(cell.chips)
    low = cell.config["check"]["control"]
    for seed in map(int, args.seeds.split(",")):
        kw = {"control": low} if "prompt" in cell.traffic else {}
        run = cell.driver.run(cell, seed=seed, seconds=args.seconds,
                              tracing=False, devices=devices,
                              t_start=time.perf_counter(), **kw)
        row = {"seed": seed, "program": {n: v for n, v, _ in run.checks}}
        if kw:
            row["control"] = {"widest_logit_gap": run.facts["control_gap"]}
        else:
            sweeps = (run.attempted + cell.traffic["warm_calls"]) * \
                cell.traffic["sweeps_per_call"]
            row["control"] = {"B_elements_differing": jacobi_control(
                cell.config, seed, sweeps, devices, low)}
        row["end_to_end"] = run.end_to_end
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
