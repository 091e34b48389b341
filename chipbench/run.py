"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads and warms the cell (set-up), measures for ``--seconds`` seconds,
checks what the timed path produced against the plain reference, and
prints one JSON line as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics; with ``--trace 1`` the per-layer ones, read from a profiled
window of at most ``TRACE_SECONDS``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its
limit.  Without a TPU, or with fewer chips than the cell needs, it
exits nonzero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

# a traced run profiles a window of at most this many seconds
TRACE_SECONDS = 10.0


def use_compile_cache() -> str:
    """The program's compile cache (``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` places it), keeping every program
    however quickly it compiled."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    harness.log(f"compile cache: {use_compile_cache()}")
    devices = harness.require_chips(cell.chips)
    tracing = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if tracing else args.seconds
    run = cell.driver.run(cell, seed=args.seed, seconds=seconds,
                          tracing=tracing, devices=devices,
                          t_start=T_START)
    line = harness.result_line(cell, run, tracing)
    harness.log(f"correct {run.correct}; attempted {run.attempted}, "
                f"failed {run.failed}")
    harness.report_checks(run.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
