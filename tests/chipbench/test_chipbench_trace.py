"""The trace reduction on a small trace recorded on a TPU v5e: one
traced window of the jacobi.1chip cell (one 1000-sweep call, 15 s),
committed under chipbench/testdata."""
import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench import trace  # noqa: E402

RECORDED = ROOT / "chipbench" / "testdata" / "jacobi_1chip.xplane.pb.gz"


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (1.5, 3), (9, 12)]) == [
        (0, 1), (3, 9)]
    assert trace.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert trace.total([(0, 1), (2, 4)]) == 3


def test_short_names_and_collectives():
    hlo = ("%jacobi_step.6 = f32[16384,16384]{1,0:T(8,128)} custom-call("
           "f32[16384,16384]{1,0} %get-tuple-element.61)")
    assert trace.short_name(hlo) == "%jacobi_step.6 custom-call"
    cp = trace.short_name("%collective-permute-start.1 = (f32[8,128]) "
                          "collective-permute-start(f32[8,128] %x)")
    assert trace.COLLECTIVE.search(cp)
    fused = trace.short_name("%fusion.3 = f32[8] fusion(f32[8] "
                             "%collective-permute-done.1)")
    assert not trace.COLLECTIVE.search(fused)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(RECORDED))


def test_window_busy_and_programs(reduced):
    r = reduced
    assert r.devices == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(15.1638, abs=1e-3)
    assert 0.99 * r.window_s < r.busy_s <= r.window_s
    scans = r.module_runs(r"jit_body")
    assert max(b - a for a, b in scans) == pytest.approx(15.103, abs=1e-2)
    assert not r.has_collectives() and r.exposed_collective_s() == 0.0


def test_breakdown(reduced):
    b = reduced.breakdown({"call"})
    names = [n for n, _ in b["device_ops"]]
    assert len(names) <= 10 and not any(n.endswith(" while") for n in names)
    assert names[0].endswith("dynamic-update-slice")
    assert any("jacobi_step" in n and n.endswith("custom-call")
               for n in names)
    per_device = sum(v for _, v in b["device_ops"])
    assert per_device <= reduced.busy_s * 1.0001
    gaps = b["idle_gaps"]
    assert gaps and all(g[0].startswith("call") for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_spans_are_the_main_threads(reduced):
    names = {n for n, _, _ in reduced.spans}
    assert "call" in names and "window" in names
    assert not any(n.startswith("tpu::") for n in names)


def test_stencil_roofline_reads_the_kernel_alone(reduced):
    """The recorded window is one 1000-sweep call.  The kernel's share
    counts only the ``%jacobi_step`` custom calls (3.659 s); the whole
    sweep's share (``mfu.jacobi``) counts the window."""
    import json
    import types

    from chipbench import harness

    config = json.loads((ROOT / "chipbench/configs/jacobi2d-16k.json")
                        .read_text())
    cell = harness.Cell("jacobi.1chip", 1, config, {}, [], [])
    run = harness.Run(
        attempted=1, failed=0, end_to_end={}, checks=[], correct=True,
        spans=harness.Spans(), trace=reduced,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        facts={"calls": 1, "sweeps_per_call": 1000, "chips": 1,
               "window_s": reduced.window_s})
    peaks = harness.load_json(ROOT / "chipbench/peaks.json")
    kernel = harness.find_reader("stencil_roofline").read(run, cell, peaks)
    whole = harness.find_reader("mfu.jacobi").read(run, cell, peaks)
    # 8 bytes per interior element at 819 GB/s, over 3.659 ms a sweep
    assert kernel == pytest.approx(100 * 8 * 16382 ** 2 / 819e9 / 3.6591e-3,
                                   rel=1e-3)
    assert 17 < whole < 18 < kernel < 100
    empty = harness.Run(**{**run.__dict__, "trace": dataclasses.replace(
        reduced, ops={d: [] for d in reduced.ops})})
    assert harness.find_reader("stencil_roofline").read(
        empty, cell, peaks) is None
