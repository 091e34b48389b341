"""Serving engine: slot lifecycle, continuous batching, determinism,
backpressure, straggler monitor, and instance failover (RecoveryEngine:
KV caches as HDArrays, fail/rejoin an instance mid-decode, token
streams must stay bit-identical)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.ft.faults import StragglerMonitor
from repro.models import build
from repro.serve import (Engine, RecoveryEngine, ServeConfig,
                         SlotsExhausted)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("yi-9b").reduced()
    bundle = build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    return Engine(bundle, params, ServeConfig(max_seq=64, slots=3,
                                              temperature=0.0))


def test_generate_and_slot_reuse(engine):
    rng = np.random.default_rng(0)
    V = engine.cfg.vocab
    p1 = rng.integers(0, V, 8)
    out1 = engine.generate(p1, 6)
    assert len(out1) == 8 + 6
    assert not engine.slot_live.any()          # slot released
    # slot is reusable and greedy decode is deterministic
    out2 = engine.generate(p1, 6)
    assert out1 == out2


def test_continuous_batching_isolation(engine):
    """A request joining mid-flight must not corrupt a running one."""
    rng = np.random.default_rng(1)
    V = engine.cfg.vocab
    pa = rng.integers(0, V, 10)
    # run A solo for the full horizon
    solo = engine.generate(pa, 8)
    # now run A again but inject another request mid-decode
    sa = engine.add_request(pa)
    for _ in range(3):
        engine.step()
    sb = engine.add_request(rng.integers(0, V, 5))
    for _ in range(4):
        engine.step()
    a_tokens = engine.finish(sa)
    engine.finish(sb)
    assert a_tokens == solo, "mid-flight join must not perturb slot A"


def test_out_of_slots(engine):
    rng = np.random.default_rng(2)
    V = engine.cfg.vocab
    sids = [engine.add_request(rng.integers(0, V, 4)) for _ in range(3)]
    # queue_depth defaults to 0: immediate typed backpressure (which
    # still subclasses the seed-era RuntimeError)
    with pytest.raises(SlotsExhausted):
        engine.add_request(rng.integers(0, V, 4))
    for s in sids:
        engine.finish(s)


def test_admission_queue_backpressure():
    cfg = get_config("yi-9b").reduced()
    bundle = build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    eng = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, temperature=0.0,
                             queue_depth=1))
    rng = np.random.default_rng(3)
    V = cfg.vocab
    pa, pb, pc = (rng.integers(0, V, n) for n in (6, 5, 4))
    sa = eng.add_request(pa)
    eng.add_request(pb)
    ticket = eng.add_request(pc)            # all slots busy -> queued
    assert ticket < 0
    assert len(eng.queue) == 1
    with pytest.raises(SlotsExhausted):     # queue full -> typed raise
        eng.add_request(rng.integers(0, V, 4))
    # the queued request must not have touched any slot
    assert eng.slot_live.all()
    # finish() drains FIFO into the freed slot and records the mapping
    eng.finish(sa)
    assert eng.admitted[ticket] == sa
    assert eng.slot_live[sa]
    # the drained request prefilled normally: deterministic decode
    solo = Engine(bundle, params,
                  ServeConfig(max_seq=64, slots=2, temperature=0.0))
    want = solo.generate(pc, 4)
    for _ in range(3):
        eng.step()
    assert eng.finish(eng.admitted[ticket]) == want


def test_instance_failover_bit_identical():
    """Fail a serving instance mid-decode with 3 live slots, rejoin it
    later: the engine shrinks (KV migrates to the survivors via a
    planned repartition), replays the checkpointed window, grows back
    on rejoin — and every request's token stream matches the
    fault-free run bit for bit."""
    cfg = get_config("yi-9b").reduced()
    bundle = build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_seq=64, slots=4, temperature=0.0)
    rng = np.random.default_rng(4)
    V = cfg.vocab
    prompts = [rng.integers(0, V, n) for n in (8, 5, 6)]

    def run(fail_at=None, rejoin_at=None):
        eng = RecoveryEngine(bundle, params, scfg, instances=3,
                             checkpoint_interval=2)
        sids = [eng.add_request(p) for p in prompts]
        for i in range(8):
            if i == fail_at:
                eng.fail_instance(1)
            if i == rejoin_at:
                eng.rejoin_instance(1)
            eng.step()
        return [eng.finish(s) for s in sids], eng

    ref, _ = run()
    out, eng = run(fail_at=3, rejoin_at=5)
    assert out == ref
    loss, join = eng.recovery_log
    assert loss["kind"] == "instance_loss" and loss["rank"] == 1
    assert loss["live"] == [0, 2] and loss["slots_live"] == 3
    assert loss["migration_bytes"] > 0      # shrink repartition moved KV
    assert loss["steps_replayed"] >= 1
    assert join["kind"] == "instance_join" and join["live"] == [0, 1, 2]
    assert join["migration_bytes"] > 0      # grow repartition moved KV
    assert eng.rt.planner.stats.elastic_shrinks == 1
    assert eng.rt.planner.stats.elastic_grows == 1
    # both migrations are planned, logged traffic
    assert any(e[0].startswith("__restore_") for e in eng.rt.comm_log)
    assert any(e[0].startswith("__repartition_") for e in eng.rt.comm_log)
    # failure without rejoin must also stream identically
    out2, eng2 = run(fail_at=2)
    assert out2 == ref
    assert eng2.recovery_log[-1]["kind"] == "instance_loss"


def test_straggler_monitor():
    m = StragglerMonitor(threshold=2.0, warmup=3)
    for i in range(8):
        assert not m.observe(i, 0.10)
    assert m.observe(8, 0.50)        # 5x the EWMA -> straggler
    assert len(m.events) == 1
    # straggler must not poison the average
    assert abs(m.ewma - 0.10) < 0.02
    assert not m.observe(9, 0.11)


def test_load_engine_cuts_depth_and_keeps_widths():
    from repro.launch.serve import load_engine

    eng = load_engine("deepseek-7b", reduced=True, n_layers=2, slots=2,
                      max_seq=32)
    ref = get_config("deepseek-7b").reduced()
    assert eng.cfg.n_layers == 2
    assert (eng.cfg.d_model, eng.cfg.n_heads, eng.cfg.d_ff,
            eng.cfg.vocab) == (ref.d_model, ref.n_heads, ref.d_ff,
                               ref.vocab)
    assert eng.params["main"]["attn"]["wq"].shape[0] == 2
    toks = eng.generate(np.arange(5), 3)
    assert len(toks) == 8 and all(0 <= t < ref.vocab for t in toks)
    with pytest.raises(ValueError):
        load_engine("deepseek-7b", reduced=True, n_layers=0)


@pytest.mark.parametrize("prefill_chunk", [None, 3])
def test_prefill_logits_give_the_first_token(prefill_chunk):
    """The last admit's last-position logits are the ones the first
    generated token was sampled from, whether the prompt was prefilled
    in one call or in chunks."""
    from repro.launch.serve import load_engine

    eng = load_engine("deepseek-7b", reduced=True, n_layers=2, slots=2,
                      max_seq=32, prefill_chunk=prefill_chunk)
    prompt = np.arange(3, 11)
    sid = eng.add_request(prompt)
    logits = np.asarray(eng.prefill_logits)
    assert logits.shape == (eng.cfg.vocab,)
    assert eng.slot_tokens[sid][-1] == int(logits.argmax())
