"""The controls at a tiny size on the CPU: the plain reference one
precision below what the configuration states, in the program's
place, must fail the comparison that the program passes."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from test_chipbench_drivers import SEED, jacobi_cell, serve_cell  # noqa: E402


def test_serve_fp8_control_fails_the_limit():
    import jax

    cell = serve_cell()
    r = cell.driver.run(cell, seed=SEED, seconds=2.0, tracing=False,
                        devices=jax.devices()[:1],
                        t_start=time.perf_counter(), control="float8_e4m3fn")
    limit = cell.config["check"]["widest_gap"]
    program = dict((n, v) for n, v, _ in r.checks)["widest_logit_gap"]
    assert program <= limit < r.facts["control_gap"]
    assert r.facts["control_gap"] >= 3 * program


def test_jacobi_bf16_control_differs():
    import jax

    from chipbench.control import jacobi_control

    cell = jacobi_cell(1)
    assert jacobi_control(cell.config, SEED, 20, jax.devices()[:1],
                          "bfloat16") > 0
