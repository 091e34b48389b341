"""The runtime's spans and counters (``repro.core.spans``).

* spans nest, and each recorded span links to the one open around it;
* with recording off nothing is kept;
* one warm 100-sweep ping-pong Jacobi call on the jax backend records
  one ``hdarray.run_pipeline``, one ``hdarray.capture``, one
  ``hdarray.replay`` and a plan, a dispatch and a commit for each of
  the 4 steps run before the scan — the same spans for 200 sweeps, so
  none per replayed step;
* a second warm call builds no program;
* the executor's programs are named after their role.
"""
import collections

import jax
import numpy as np
import pytest

from repro.core import AccessSpec, Box, HDArrayRuntime, spans
from repro.kernels.hd import make_jacobi_kernel

FOUR = AccessSpec.of((0, -1), (0, 1), (-1, 0), (1, 0), (0, 0))
IDENT = AccessSpec.of((0, 0))
STEPS_BEFORE_SCAN = 4        # two periods of the ping-pong: the witness


@pytest.fixture
def recorder():
    spans.clear()
    spans.record(True)
    yield spans
    spans.record(False)
    spans.clear()


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} host devices (XLA_FLAGS not applied?)")


def _jacobi(nproc=4, n=64):
    """A runtime holding the ping-pong Jacobi of the benchmark (jnp
    stencil), and a function making ``sweeps`` steps of it."""
    rt = HDArrayRuntime(nproc, backend="jax")
    a, b = rt.create("A", (n, n)), rt.create("B", (n, n))
    data = rt.partition_row((n, n))
    work = rt.partition_row((n, n), region=Box.make((1, n - 1), (1, n - 1)))
    x0 = np.random.default_rng(0).random((n, n), dtype=np.float32)
    rt.write(a, x0, data)
    rt.write(b, x0, data)
    k_ab = make_jacobi_kernel("B", "A", impl="ref")
    k_ba = make_jacobi_kernel("A", "B", impl="ref")

    def steps(sweeps):
        return [dict(kernel_name="jac_ab", part_id=work, kernel=k_ab,
                     arrays=[a, b], uses={"B": FOUR}, defs={"A": IDENT})
                if i % 2 == 0 else
                dict(kernel_name="jac_ba", part_id=work, kernel=k_ba,
                     arrays=[a, b], uses={"A": FOUR}, defs={"B": IDENT})
                for i in range(sweeps)]
    return rt, steps


def test_spans_nest_and_link_to_their_parent(recorder):
    with spans.span("outer"):
        with spans.span("a"):
            with spans.span("inner"):
                pass
        with spans.span("b"):
            pass
    got = {s.name: s for s in recorder.snapshot()}
    assert [s.name for s in recorder.snapshot()] == ["inner", "a", "b",
                                                     "outer"]
    assert got["outer"].parent is None
    assert got["a"].parent == got["b"].parent == got["outer"].sid
    assert got["inner"].parent == got["a"].sid
    for s in got.values():
        assert s.start <= s.end
    assert got["outer"].start <= got["a"].start <= got["inner"].start
    assert got["inner"].end <= got["a"].end <= got["b"].start
    assert got["b"].end <= got["outer"].end


def test_recording_off_keeps_nothing():
    spans.clear()
    spans.record(False)
    with spans.span("hdarray.run_pipeline"):
        with spans.span("hdarray.plan"):
            pass
    assert spans.snapshot() == []


def test_a_span_closes_on_an_exception(recorder):
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("fails"):
                raise ValueError("x")
    with spans.span("after"):
        pass
    got = {s.name: s for s in recorder.snapshot()}
    assert got["fails"].parent == got["outer"].sid
    assert got["after"].parent is None


def _one_call(rt, steps, sweeps):
    spans.clear()
    rt.run_pipeline(steps(sweeps))
    return spans.snapshot()


@pytest.mark.parametrize("sweeps", [100, 200])
def test_one_call_records_its_phases_once(recorder, sweeps):
    _need_devices(4)
    rt, steps = _jacobi()
    rt.run_pipeline(steps(sweeps))                  # cold: builds programs
    st = rt.planner.stats
    fused0, captured0 = st.fused_steps, st.captured_steps
    got = _one_call(rt, steps, sweeps)
    counts = collections.Counter(s.name for s in got)
    assert counts == {"hdarray.run_pipeline": 1, "hdarray.capture": 1,
                      "hdarray.replay": 1,
                      "hdarray.plan": STEPS_BEFORE_SCAN,
                      "hdarray.dispatch": STEPS_BEFORE_SCAN,
                      "hdarray.commit": STEPS_BEFORE_SCAN}
    # the spans' count is the counters' too: one dispatch span per
    # step outside the scan, the others inside it
    assert st.fused_steps - fused0 == STEPS_BEFORE_SCAN
    assert st.captured_steps - captured0 == sweeps - STEPS_BEFORE_SCAN
    assert len(got) <= 20
    call = next(s for s in got if s.name == "hdarray.run_pipeline")
    for s in got:
        if s is not call:
            assert s.parent == call.sid, s
            assert call.start <= s.start <= s.end <= call.end
    rt.close()


def test_a_warm_call_builds_no_program():
    _need_devices(4)
    rt, steps = _jacobi()
    ex = rt.executor
    rt.run_pipeline(steps(100))
    built = ex.programs_built
    assert built >= 2                   # step programs and the scan
    rt.run_pipeline(steps(100))         # steady from its first step
    warm = ex.programs_built
    rt.run_pipeline(steps(100))
    assert ex.programs_built == warm
    # and no full buffer crossed since the writes
    assert ex.h2d_transfers == 2 and ex.d2h_transfers == 0
    assert ex.h2d_bytes == 2 * 4 * 64 * 64 * 4 and ex.d2h_bytes == 0
    rt.read_coherent(rt.arrays["B"])
    assert ex.d2h_transfers == 1 and ex.d2h_bytes == 4 * 64 * 64 * 4
    rt.close()


def test_write_and_read_have_spans(recorder):
    rt = HDArrayRuntime(2, backend="sim")
    a = rt.create("A", (8, 8))
    part = rt.partition_row((8, 8))
    rt.write(a, np.ones((8, 8), np.float32), part)
    rt.read(a, part)
    rt.read_coherent(a)
    assert [s.name for s in recorder.snapshot()] == [
        "hdarray.write", "hdarray.read", "hdarray.read"]
    rt.close()


def _module_names(ex):
    """The lowered module name of every step and scan program built."""
    names = set()
    for key, prog in ex._programs.items():
        if key[0] == "step" and prog[0] == "fused":
            fn, arrays = prog[1], key[4]
        elif key[0] == "scan":
            fn, arrays = prog[0], key[3]
        else:
            continue
        avals = [jax.ShapeDtypeStruct((ex.nproc,) + shape, np.dtype(dt))
                 for _name, shape, dt in arrays]
        text = fn.lower(*avals).as_text()
        names.add(text.split("module @", 1)[1].split(" ", 1)[0])
    return names


def test_programs_are_named_by_role(monkeypatch):
    _need_devices(4)
    from repro.executors import jax_exec

    # the accelerator's program shapes: one fused program a step
    monkeypatch.setattr(jax_exec, "cpu_host_platform", lambda: False)
    rt, steps = _jacobi()
    rt.run_pipeline(steps(12))
    names = _module_names(rt.executor)
    assert names == {"jit_hdarray_step", "jit_hdarray_scan"}, names
    assert not any("body" in n for n in names)
    rt.close()
