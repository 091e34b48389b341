"""The readers of the program's own spans (``plan_ms``, ``replay_ms``,
``dispatch_ms``, ``host_pace_pct``) on a synthetic reduced trace: two
``run_pipeline`` calls whose spans nest as the runtime's do, among the
Python profiler's frames and the benchmark's own spans."""
import dataclasses
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench import harness, program_spans, trace  # noqa: E402

DEV = "/device:TPU:0"


def _call(t):
    """One call's host events from time t (seconds): 2 eager steps,
    the second under the overlap schedule (its commit inside its
    dispatch), then the scan and the replay."""
    return [
        ("call", t, t + 0.60),
        ("hdarray.run_pipeline", t + 0.01, t + 0.51),
        ("hdarray.plan", t + 0.01, t + 0.02),
        ("$planner.py:251 plan", t + 0.011, t + 0.019),
        ("hdarray.dispatch", t + 0.02, t + 0.05),
        ("hdarray.commit", t + 0.05, t + 0.06),
        ("hdarray.plan", t + 0.06, t + 0.07),
        ("hdarray.dispatch", t + 0.07, t + 0.11),
        ("hdarray.commit", t + 0.08, t + 0.09),
        ("hdarray.capture", t + 0.11, t + 0.13),
        ("hdarray.replay", t + 0.13, t + 0.50),
        ("$runtime.py:380 _replay_step_metadata", t + 0.14, t + 0.15),
    ]


@pytest.fixture
def reduced():
    events = [("window", 0.0, 10.0)] + _call(1.0) + _call(3.0)
    return trace.Reduced(window=(0.0, 10.0),
                         ops={DEV: [("%fusion.1 fusion", 0.5, 8.5)]},
                         async_ops={}, modules={}, spans=events)


def _run(reduced):
    return harness.Run(attempted=2, failed=0, end_to_end={}, checks=[],
                       correct=True, spans=harness.Spans(), trace=reduced,
                       devices=[types.SimpleNamespace(
                           device_kind="TPU v5 lite")],
                       facts={"calls": 2, "sweeps_per_call": 100,
                              "chips": 1, "window_s": 10.0})


def _read(name, reduced):
    cell = harness.Cell("jacobi.1chip", 1, {}, {}, [], [])
    return harness.find_reader(name).read(_run(reduced), cell, {})


def test_self_time_leaves_out_nested_program_spans():
    spans = [("hdarray.run_pipeline", 0.0, 1.0),
             ("hdarray.dispatch", 0.1, 0.5),
             ("hdarray.commit", 0.2, 0.3),
             ("hdarray.replay", 0.5, 0.9)]
    assert program_spans.self_seconds(spans) == pytest.approx(
        [0.2, 0.3, 0.1, 0.4])


@pytest.mark.parametrize("name,per_call", [
    # plan 0.01 + 0.01, commit 0.01 + 0.01 (one inside a dispatch)
    ("plan_ms", 40.0),
    ("replay_ms", 370.0),
    # dispatch 0.03 + (0.04 less its commit's 0.01), capture 0.02
    ("dispatch_ms", 80.0),
])
def test_per_call_readings(reduced, name, per_call):
    assert _read(name, reduced) == pytest.approx(per_call)


def test_host_pace_is_call_time_over_device_busy_time(reduced):
    # two 0.5 s calls against 8 s of device work
    assert _read("host_pace_pct", reduced) == pytest.approx(12.5)
    two_chips = dataclasses.replace(
        reduced, ops={DEV: [("a", 0.5, 8.5)],
                      "/device:TPU:1": [("a", 0.5, 4.5)]})
    assert _read("host_pace_pct", two_chips) == pytest.approx(
        100 * 1.0 / 6.0)
    idle = dataclasses.replace(reduced, ops={DEV: []})
    assert _read("host_pace_pct", idle) is None


def test_a_call_normalizes_every_reading(reduced):
    one = dataclasses.replace(
        reduced, spans=[s for s in reduced.spans if s[1] < 2.0])
    for name in ("plan_ms", "replay_ms", "dispatch_ms"):
        assert _read(name, one) == pytest.approx(_read(name, reduced))
    assert _read("host_pace_pct", one) == pytest.approx(
        _read("host_pace_pct", reduced) / 2)


@pytest.mark.parametrize("name", ["plan_ms", "replay_ms", "dispatch_ms",
                                  "host_pace_pct"])
def test_nothing_to_read_without_program_spans(reduced, name):
    bare = dataclasses.replace(reduced, spans=[
        s for s in reduced.spans if not s[0].startswith("hdarray.")])
    assert _read(name, bare) is None


def test_a_trace_of_a_program_without_spans_reads_nothing():
    """The v5e trace recorded before the program had spans."""
    old = trace.reduce(str(ROOT / "chipbench/testdata/"
                           "jacobi_1chip.xplane.pb.gz"))
    for name in ("plan_ms", "replay_ms", "dispatch_ms", "host_pace_pct"):
        assert _read(name, old) is None


def test_a_recorded_trace_with_the_programs_spans():
    """A v5e trace of jacobi.1chip's traced window (11 calls of 100
    sweeps, 16.67 s) recorded with the program's spans: the readers
    give what that run printed, and the executor's programs are named
    by role."""
    import json

    rec = trace.reduce(str(ROOT / "chipbench/testdata/"
                           "jacobi_1chip_spans.xplane.pb.gz"))
    printed = {"plan_ms": 0.4439774545458499,
               "replay_ms": 5.305383909090863,
               "dispatch_ms": 1.21346990909081,
               "host_pace_pct": 0.5080708491202582}
    for name, value in printed.items():
        assert _read(name, rec) == pytest.approx(value, rel=1e-9), name
    modules = {n.split("(")[0] for runs in rec.modules.values()
               for n, _, _ in runs}
    assert {"jit_hdarray_step", "jit_hdarray_scan"} <= modules
    assert not any("body" in m for m in modules)
    # the stencil keeps its name, and its roofline share its reading
    config = json.loads((ROOT / "chipbench/configs/jacobi2d-16k.json")
                        .read_text())
    cell = harness.Cell("jacobi.1chip", 1, config, {}, [], [])
    run = dataclasses.replace(_run(rec), facts={
        "calls": 11, "sweeps_per_call": 100, "chips": 1,
        "window_s": rec.window_s})
    peaks = harness.load_json(ROOT / "chipbench/peaks.json")
    assert harness.find_reader("stencil_roofline").read(
        run, cell, peaks) == pytest.approx(71.65280708511274, rel=1e-9)


@pytest.mark.parametrize("cell, names", [
    ("jacobi.1chip", {"plan_ms", "replay_ms", "dispatch_ms",
                      "host_pace_pct"}),
    ("jacobi.4chip", {"plan_ms", "replay_ms"}),
])
def test_which_span_readings_each_cell_reports(cell, names):
    """On four chips a launch can wait for the chip once more calls are
    ahead than the runtime keeps in flight, and that wait falls in
    ``dispatch_ms`` and ``host_pace_pct``: only the one-chip cell, whose
    calls ahead stay under the cap, reports them."""
    reported = {m["name"] for m in harness.find_cell(cell).per_layer}
    assert reported & {"plan_ms", "replay_ms", "dispatch_ms",
                       "host_pace_pct"} == names
