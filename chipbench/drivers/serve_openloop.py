"""Driver ``serve_openloop``: the slot ``Engine`` under open-loop traffic.

Set-up makes the weights on the device from the seed in one jitted
call (float32, the layout the program's model takes), builds
``Engine`` with the configuration's slots, cache and prefill chunk,
and warms every program the window will run: a prefill chunk into
each slot, an admission from the queue, decode steps.

The window sends each request when it is due (``openloop.py``),
admits it through ``Engine.add_request`` (or the engine's queue,
drained on ``finish``), steps the engine while any slot is live, and
times each token on the host clock.  After the window no request is
sent; those still open are served for up to ``DRAIN_S`` more seconds.
``tpot_ms`` is the time per output token after the first, (last token
- first token) / (tokens - 1), averaged over every request due in the
window; ``out_tok_s`` the tokens produced inside the window over it.
The TTFT (from the due time) and token-gap percentiles go to an
earlier line.

``correct``: a sample of finished requests drawn from the seed, the
longest among them, of at least ``check_tokens`` served tokens, goes
through the plain reference (``refs/llama.py``) once the engine is
freed; the widest gap by which a served token's reference logit lies
below the reference's best must stay under the configuration's limit,
and every request due in the window must finish.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from chipbench import harness, openloop
from chipbench.refs import llama

DRAIN_S = 60.0


def arch_config(config: dict):
    """The program's configuration of ``arch`` at the file's sizes."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(config["arch"]), n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_base=config["rope_theta"])


def init_weights(dims: tuple, key):
    """Float32 weights in the program's layout.  Matrices are
    N(0, initializer_range); a norm's stored offset w (scale 1 + w) is
    N(0, 0.1)."""
    import jax
    import jax.numpy as jnp

    L, D, HD, KV, F, V, std = dims
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    return {
        "emb": {"in_emb": normal((V, D), std), "out_emb": normal((D, V), std),
                "final_norm": normal((D,), 0.1)},
        "main": {
            "attn": {"wq": normal((L, D, HD), std),
                     "wk": normal((L, D, KV), std),
                     "wv": normal((L, D, KV), std),
                     "wo": normal((L, HD, D), std)},
            "norms": {"pre_attn": normal((L, D), 0.1),
                      "pre_mlp": normal((L, D), 0.1)},
            "ffn": {"w_gate": normal((L, D, F), std),
                    "w_up": normal((L, D, F), std),
                    "w_down": normal((L, F, D), std)}}}


def reference_view(p):
    """The weights under the reference's names (the program stores a
    norm's scale as 1 + w)."""
    m = p["main"]
    return {"embed": p["emb"]["in_emb"], "head": p["emb"]["out_emb"],
            "final_norm": 1.0 + p["emb"]["final_norm"],
            "q": m["attn"]["wq"], "k": m["attn"]["wk"], "v": m["attn"]["wv"],
            "o": m["attn"]["wo"], "gate": m["ffn"]["w_gate"],
            "up": m["ffn"]["w_up"], "down": m["ffn"]["w_down"],
            "attn_norm": 1.0 + m["norms"]["pre_attn"],
            "mlp_norm": 1.0 + m["norms"]["pre_mlp"]}


def jax_key(seed: int):
    import jax

    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1)))


def build(config: dict, seed: int):
    """(params, engine) for the configuration, weights from the
    seed."""
    import jax

    from repro.models import build as build_model
    from repro.serve import Engine, ServeConfig

    cfg = arch_config(config)
    bundle = build_model(cfg)
    dims = (cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.head_dim,
            cfg.n_kv_heads * cfg.head_dim, cfg.d_ff, cfg.vocab,
            config["initializer_range"])
    init = jax.jit(functools.partial(init_weights, dims))
    key = jax_key(seed)
    want = jax.eval_shape(lambda: bundle.init(key)[0])
    have = jax.eval_shape(init, key)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")
    params = jax.block_until_ready(init(key))
    s = config["serve"]
    eng = Engine(bundle, params,
                 ServeConfig(max_seq=s["max_seq"], slots=s["slots"],
                             temperature=0.0, queue_depth=1 << 30,
                             prefill_chunk=s["prefill_chunk"]), seed=0)
    return params, eng


def warm(eng, config: dict, rng) -> None:
    """Run every program the window runs: a chunk into each slot (the
    slot is a static index of some of the engine's ops), a queued
    request admitted on ``finish``, decode steps."""
    s = config["serve"]
    chunk, vocab = s["prefill_chunk"], config["vocab_size"]

    def prompt(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    sids = [eng.add_request(prompt(chunk * (1 + i % 2)))
            for i in range(s["slots"])]
    eng.add_request(prompt(chunk))
    eng.step()
    eng.finish(sids[0])
    eng.step()
    for sid in np.flatnonzero(eng.slot_live):
        eng.finish(int(sid))


def serve(eng, reqs, seconds: float, spans: harness.Spans) -> float:
    """The open loop.  Fills each request's ``sent``, ``times`` and
    ``served``; returns when all are served or ``DRAIN_S`` after the
    window.  Times are seconds from the window's start."""
    clock = time.perf_counter
    w0 = clock()
    live, waiting = {}, {}
    i = 0

    def admitted(r, sid):
        r.slot = sid
        r.times.append(clock() - w0)
        live[sid] = r

    while True:
        now = clock() - w0
        while i < len(reqs) and reqs[i].due <= now:
            r = reqs[i]
            i += 1
            r.sent = now
            if not eng.slot_live.all():
                with spans("admit", tokens=len(r.prompt)):
                    sid = eng.add_request(r.prompt)
                admitted(r, sid)
            else:
                waiting[eng.add_request(r.prompt)] = r
            now = clock() - w0
        if live:
            pos = [int(eng.slot_pos[s]) for s in live]
            with spans("decode", pos=pos):
                out = eng.step()
            t = clock() - w0
            for s in out:
                live[s].times.append(t)
            for s in [s for s, r in live.items() if len(r.times) >= r.out_len]:
                r = live.pop(s)
                if eng.queue:
                    with spans("admit", tokens=len(eng.queue[0][1])):
                        toks = eng.finish(s)
                    tk = next(t for t in eng.admitted if t in waiting)
                    admitted(waiting.pop(tk), eng.admitted.pop(tk))
                else:
                    toks = eng.finish(s)
                r.served = toks[len(r.prompt):][:r.out_len]
        elif i >= len(reqs):
            break
        else:
            time.sleep(max(0.0, reqs[i].due - (clock() - w0)))
        if clock() - w0 > seconds + DRAIN_S:
            break
    return clock() - w0


def run(cell: harness.Cell, *, seed: int, seconds: float, tracing: bool,
        devices, t_start: float, control=None) -> harness.Run:
    from chipbench import trace

    config, mix = cell.config, cell.traffic
    spans = harness.Spans(tracing)
    counter = harness.CompileCounter()
    reqs = openloop.requests(mix, seed, seconds, config["vocab_size"])
    params, eng = build(config, seed)
    warm(eng, config, np.random.default_rng(seed + 1))

    counter.counting = True
    with trace.recording(tracing) as found:
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        with spans(trace.WINDOW):
            end = serve(eng, reqs, seconds, spans)
    counter.counting = False
    late = [r.sent - r.due for r in reqs if r.sent is not None]
    harness.log(f"window: {len(reqs)} requests due in {seconds} s, served "
                f"until {end:.3f} s; generator late by median "
                f"{1e3 * openloop.percentile(late, 0.5):.3f} ms, max "
                f"{1e3 * max(late):.3f} ms; programs lowered "
                f"{counter.lowered}, compiled {counter.compiled}")
    reduced = None
    if tracing:
        reduced = trace.reduce(found[0])
        trace.discard(found)
    memory = harness.memory_peak(devices)

    ttft = [(r.times[0] if r.times else end) - r.due for r in reqs]
    gaps = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
            if b <= seconds]
    tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1) for r in reqs
            if len(r.times) > 1]
    tokens = sum(t <= seconds for r in reqs for t in r.times)
    failed = sum(r.served is None for r in reqs)
    e2e = {"tpot_ms": 1e3 * sum(tpot) / len(tpot),
           "out_tok_s": tokens / seconds, "setup_s": setup_s}
    pct = openloop.percentile
    harness.log(
        f"ttft p50 {1e3 * pct(ttft, 0.5):.3f} ms, p80 "
        f"{1e3 * pct(ttft, 0.8):.3f} ms, p90 {1e3 * pct(ttft, 0.9):.3f} ms "
        f"over {len(ttft)} requests; token gaps {len(gaps)}, median "
        f"{1e3 * pct(gaps, 0.5):.3f} ms, p99 {1e3 * pct(gaps, 0.99):.3f} ms; "
        f"time per output token, mean over requests "
        f"{e2e['tpot_ms']:.3f} ms; tokens in the window {tokens}")

    del eng
    gc.collect()
    done = [r for r in reqs if r.served is not None]
    picked = choose(done, config["check"]["tokens"], seed)
    t_ref = time.perf_counter()
    widest, ctl = check(params, config, picked, control=control)
    harness.log(f"reference: {len(picked)} requests, "
                f"{sum(len(r.served) for r in picked)} served tokens in "
                f"{time.perf_counter() - t_ref:.3f} s")
    checks = [("widest_logit_gap", widest, config["check"]["widest_gap"]),
              ("requests_unfinished", failed, 0)]
    del params
    return harness.Run(
        attempted=len(reqs), failed=failed, end_to_end=e2e, checks=checks,
        correct=harness.within(checks), spans=spans,
        devices=devices, trace=reduced, memory_peak_bytes=memory,
        facts={"control_gap": ctl})


def choose(done, tokens: int, seed: int):
    """The longest finished request, then others in an order drawn from
    the seed, until ``tokens`` served tokens are covered."""
    if not done:
        return []
    order = sorted(done, key=lambda r: -len(r.served))
    rest = order[1:]
    rest = [rest[i] for i in np.random.default_rng(seed + 2).permutation(
        len(rest))]
    picked = [order[0]]
    for r in rest:
        if sum(len(p.served) for p in picked) >= tokens:
            break
        picked.append(r)
    return picked


def check(params, config: dict, picked, control=None):
    """The widest reference-logit gap of the served tokens; with
    ``control`` (a lower precision) also the control's widest gap at
    the same positions.  Returns (widest, control widest or None)."""
    import jax
    import jax.numpy as jnp

    if not picked:
        return float("inf"), None
    T = config["serve"]["max_seq"]
    rcfg = {k: config[k] for k in ("num_attention_heads", "head_dim",
                                   "rms_norm_eps", "rope_theta")}
    ref = jax.jit(lambda p, s: llama.served_gaps(reference_view(p), s, rcfg))
    ctl = (jax.jit(lambda p, s: llama.control_gaps(reference_view(p), s,
                                                   rcfg, control))
           if control else None)
    widest, cwidest = 0.0, 0.0
    for r in picked:
        seq = np.zeros(T, np.int32)
        P, n = len(r.prompt), len(r.served)
        seq[:P] = r.prompt
        seq[P:P + n] = r.served
        with jax.default_matmul_precision("highest"):
            g = np.asarray(ref(params, jnp.asarray(seq)))[P - 1:P + n - 1]
            widest = max(widest, float(g.max()))
            if ctl is not None:
                c = np.asarray(ctl(params, jnp.asarray(seq)))[P - 1:P + n - 1]
                cwidest = max(cwidest, float(c.max()))
    return widest, (cwidest if ctl is not None else None)
