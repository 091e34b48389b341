"""Each driver rehearsed on the CPU at a tiny size, through the test
entry (``driver.run`` with CPU devices, never ``run.py``'s chip
check): a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct, once for each fault the cell
can have."""
import copy
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench import harness  # noqa: E402

SEED = 2 ** 33 + 5


def jacobi_cell(p):
    config = {"grid_rows": 64, "grid_cols": 256, "devices": p,
              "check": {"control": "bfloat16"}}
    traffic = {"driver": "hdarray_pipeline", "sweeps_per_call": 10,
               "warm_calls": 2, "ahead_s": 0.05}
    return harness.Cell("jacobi.tiny", p, config, traffic, [], [])


def serve_cell():
    """A LLaMA-architecture decoder (the program's ``deepseek-7b``) at
    tiny widths under a short open-loop mix.  No benchmark cell serves
    yet: this rehearses the serving driver for the cell that will.  The
    limit is set from readings at this size: the program's widest gap
    read 0.0015 and the fp8 control's 0.012 on SEED (CPU)."""
    config = {"arch": "deepseek-7b", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 4, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 256,
              "initializer_range": 0.02, "rms_norm_eps": 1e-06,
              "rope_theta": 10000.0,
              "serve": {"slots": 4, "max_seq": 128, "prefill_chunk": 16},
              "check": {"tokens": 40, "control": "float8_e4m3fn",
                        "widest_gap": 0.005}}
    traffic = {"driver": "serve_openloop", "rate_per_s": 8,
               "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
               "prompt_multiple": 16,
               "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
               "schedule_seed": 20261016}
    return harness.Cell("serve.tiny", 1, config, traffic, [], [])


def run(cell, seconds, **kw):
    import jax

    return cell.driver.run(cell, seed=SEED, seconds=seconds, tracing=False,
                           devices=jax.devices()[:cell.chips],
                           t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("p", [1, 4])
def test_jacobi_sound(p):
    r = run(jacobi_cell(p), 0.3, interpret=True)
    assert r.correct and r.attempted >= 1 and r.failed == 0
    assert [v for _, v, _ in r.checks] == [0, 0]
    assert r.end_to_end["step_ms"] > 0 and r.end_to_end["setup_s"] > 0


def test_jacobi_window_keeps_calls_ahead():
    """With more than one call dispatched ahead, every call made is
    waited for before the clock is read, and the answer stays exact."""
    cell = jacobi_cell(1)
    cell.traffic["ahead_s"] = 60.0
    r = run(cell, 0.3, interpret=True)
    assert r.correct and r.facts["calls_ahead"] > 1
    names = [rec[0] for rec in r.spans.records]
    assert names.count("call") == r.attempted and names[-2:] == [
        "drain", "window"]
    overhead = harness.find_reader("call_overhead_ms").read(r, cell, None)
    assert 0 < overhead < 1e3 * r.facts["window_s"]


def _unchanged_kernel(src, dst, **_kw):
    from repro.executors import device_kernel

    @device_kernel
    def kernel(region, bufs):
        return {dst: bufs[dst]}
    return kernel


def _altered_step(orig):
    def step(x, **kw):
        return orig(x, **kw).at[1, 1].add(1.0)
    return step


def _no_exchange(self, plan, by_name):
    return []


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "exchange_left_out"])
def test_jacobi_fault_is_caught(fault, monkeypatch):
    import repro.kernels.hd as hd
    import repro.kernels.stencil_hd.ops as ops
    from repro.executors.jax_exec import JaxExecutor

    p = 1
    if fault == "state_unchanged":
        monkeypatch.setattr(hd, "make_jacobi_kernel", _unchanged_kernel)
    elif fault == "answer_altered":
        monkeypatch.setattr(ops, "jacobi_step", _altered_step(ops.jacobi_step))
    else:
        p = 4
        monkeypatch.setattr(JaxExecutor, "_plan_groups", _no_exchange)
    r = run(jacobi_cell(p), 0.3, interpret=True)
    assert not r.correct
    assert max(v for _, v, _ in r.checks) > 0


def test_serve_sound():
    r = run(serve_cell(), 2.0)
    checks = {n: (v, lim) for n, v, lim in r.checks}
    assert r.correct and r.failed == 0 and r.attempted == 16
    assert checks["widest_logit_gap"][0] <= 0.005
    e = r.end_to_end
    assert e["tpot_ms"] > 0 and e["out_tok_s"] > 0


def _frozen_decode(bundle):
    def decode_step(params, batch, cache):
        logits, _ = bundle.decode(params, batch, cache)
        return logits, cache
    return decode_step


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_serve_fault_is_caught(fault, monkeypatch):
    import repro.serve.engine as engine

    if fault == "state_unchanged":
        monkeypatch.setattr(engine, "make_decode_step", _frozen_decode)
    else:
        orig = engine.Engine._sample
        calls = []

        def sample(self, logits):
            toks = np.array(orig(self, logits))
            calls.append(1)
            if len(calls) % 7 == 0:
                toks = (toks + 1) % self.cfg.vocab
            return toks
        monkeypatch.setattr(engine.Engine, "_sample", sample)
    r = run(serve_cell(), 2.0)
    assert not r.correct
    assert dict((n, v) for n, v, _ in r.checks)["widest_logit_gap"] > 0.005


def test_openloop_same_work_for_every_seed():
    from chipbench import openloop

    mix = copy.deepcopy(serve_cell().traffic)
    a = openloop.requests(mix, 1, 20.0, 256)
    b = openloop.requests(mix, 2 ** 40 + 3, 20.0, 256)
    assert len(a) == len(b) == 160
    assert [(r.due, len(r.prompt), r.out_len) for r in a] == [
        (r.due, len(r.prompt), r.out_len) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert all(len(r.prompt) % 16 == 0 for r in a)
    assert max(r.due for r in a) < 20.0
    again = openloop.requests(mix, 1, 20.0, 256)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    other = openloop.requests(dict(mix, schedule_seed=7), 1, 20.0, 256)
    assert [r.due for r in other] != [r.due for r in a]
    assert sorted(r.out_len for r in other) == sorted(r.out_len for r in a)
