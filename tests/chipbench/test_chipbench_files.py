"""The benchmark's files: what BENCHMARK.json names exists and parses,
its entries keep to the benchmark's rules, and a cell, configuration,
mix or per-layer metric is added by adding files alone."""
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fit_a_full_check():
    s = BENCH["run_seconds"]
    assert 1 <= s <= 51
    # 24 cells: 2 + 14 * cells runs of s + 60 s, 2 x 90 s each to compile,
    # 1200 s spare, within 43200 s
    assert (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_keys(kind):
    entries = BENCH[kind]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[kind]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        assert set(e) - {"workloads"} == keys, e["name"]
        for text in ("why", "layer", "source"):
            if text in e:
                assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        if "bound" in e:
            assert 0.01 <= e["bound"] <= 0.25
        if kind in ("end_to_end", "per_layer"):
            allowed = ({"host_clock", "device_trace"} if kind == "end_to_end"
                       else {"host_clock", "device_trace", "program_span",
                             "program_counter"})
            assert e["source"] in allowed
        if kind == "configs":
            assert len(e["reduced"]) <= 16
            assert all(NAME.match(k) for k in e["reduced"])
            assert not any(k.endswith(("_dim", "_rank", "_size"))
                           for k in e["reduced"])
        if kind == "workloads":
            assert e["chips"] in (1, 4) and NAME.match(e["traffic"])


def test_every_named_file_is_found_and_parses():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert c["file"].startswith("chipbench/")
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for name in CELLS:
        cell = harness.find_cell(name, BENCH)
        assert cell.driver.run
        for m in cell.per_layer:
            assert callable(harness.find_reader(m["name"]).read)
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_pairs_chips_and_setup():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_its_layers_move(cell):
    c = harness.find_cell(cell, BENCH)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported, (m["name"], cell)
    for m in BENCH["per_layer"]:
        assert all(w in CELLS for w in m.get("workloads", []))


def test_rooflines_and_mfu_are_named_so():
    for m in BENCH["per_layer"]:
        if m["unit"] == "%" and "idle" not in m["name"]:
            assert m["name"].endswith("_roofline") or "mfu" in m["name"]


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """Copy the benchmark, add a configuration, a mix, a cell and a
    per-layer metric as new files and entries: the harness finds all of
    them, and no file that was there changes."""
    work = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", work / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (work / "chipbench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    conf = json.loads((ROOT / "chipbench/configs/jacobi2d-16k.json")
                      .read_text())
    conf["name"] = "jacobi2d-8k"
    conf["grid_rows"] = conf["grid_cols"] = 8192
    (work / "chipbench/configs/jacobi2d-8k.json").write_text(
        json.dumps(conf))
    mix = json.loads((ROOT / "chipbench/traffic/sweeps100.json")
                     .read_text())
    mix["sweeps_per_call"] = 400
    (work / "chipbench/traffic/sweeps400.json").write_text(json.dumps(mix))
    (work / "chipbench/metrics/halo_rows_per_sweep.py").write_text(
        "def read(run, cell, peaks):\n    return 1.0\n")
    bench["configs"].append({"name": "jacobi2d-8k", "source": "x",
                             "file": "chipbench/configs/jacobi2d-8k.json",
                             "reduced": conf["reduced"], "why": "x"})
    bench["workloads"].append({"name": "jacobi8k.long", "config":
                               "jacobi2d-8k", "traffic": "sweeps400",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if "step_ms" == m["name"]:
            m["workloads"].append("jacobi8k.long")
    bench["per_layer"].append({"name": "halo_rows_per_sweep",
                               "unit": "rows", "better": "lower",
                               "source": "program_counter",
                               "layer": "executor", "moves": "step_ms",
                               "workloads": ["jacobi8k.long"]})
    (work / "BENCHMARK.json").write_text(json.dumps(bench))
    old_root, old_here = harness.ROOT, harness.HERE
    harness.ROOT, harness.HERE = work, work / "chipbench"
    try:
        cell = harness.find_cell("jacobi8k.long")
        assert cell.config["grid_rows"] == 8192
        assert cell.traffic["sweeps_per_call"] == 400
        assert cell.driver.__name__.endswith("hdarray_pipeline")
        assert [m["name"] for m in cell.per_layer] == ["halo_rows_per_sweep"]
        run = harness.Run(attempted=1, failed=0, end_to_end={}, checks=[],
                          correct=True, spans=harness.Spans(), facts={},
                          devices=[])
        assert harness.read_per_layer(cell, run) == {
            "halo_rows_per_sweep": {"value": 1.0, "unit": "rows"}}
    finally:
        harness.ROOT, harness.HERE = old_root, old_here
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_split_metric_shares_the_reader_of_its_first_part():
    idle = harness.find_reader("idle_pct.array")
    assert idle.__file__.endswith("metrics/idle_pct.py")
    assert harness.find_reader("idle_pct.serve").__file__ == idle.__file__
    own = harness.find_reader("mfu.jacobi")
    assert own.__file__.endswith("metrics/mfu.jacobi.py")
