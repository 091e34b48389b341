"""The benchmark's command refuses to measure anywhere but on a TPU, and
in a directory that holds only the benchmark's own files."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cwd / ".jax_cache"))
    cmd = BENCH["command"] + ["--workload", cell, "--seed", str(2 ** 31 + 7),
                              "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=240)


def test_no_tpu_no_result():
    for w in BENCH["workloads"]:
        p = _run(ROOT, w["name"])
        assert p.returncode != 0, p.stdout
        assert '"metrics"' not in p.stdout and "needs a TPU" in p.stderr


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, BENCH["workloads"][0]["name"])
    assert p.returncode != 0 and '"metrics"' not in p.stdout
