"""Chip smoke test: the system's main paths once, on a TPU, at real sizes.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the HDArray mesh path

One chip runs, in order:

* serve   — ``launch.serve.load_engine`` on deepseek-7b at its published
  widths with the depth cut to 8 of 30 layers (params are float32 from
  the seed, so all 30 layers would need 27.6 GB); 4 slots, a 1024-token
  cache, greedy, prompts prefilled in 64-token chunks.  4 prompts of
  128 tokens, 32 new tokens each, then a two-turn conversation: a
  100-token prompt (its last chunk is ragged), then that prompt, its
  output and 28 more tokens.  Checks: both programs fit the chip, token
  ids are in range, a repeated request streams the same tokens, and
  the last-position logits of the engine's chunked prefill match
  ``bundle.forward``.
* pool    — ``ReplicaPool`` with 2 replicas, prefix reuse and the
  prefix-aware router on the same requests (two prompts share a
  64-token prefix; the second turn extends the first): every token
  stream must be bit-identical to the single engine's, which copies
  nothing, and the second turn may reuse only the first turn's whole
  chunk, never its ragged chunk or decoded rows.
* hdarray — ``HDArrayRuntime(1, backend="jax")``: ping-pong Jacobi with
  the Pallas stencil at 16384² f32 and row-band GEMM with the Pallas
  GEMM at 8192² f32, through ``run_pipeline``, against numpy.

``--chips 4`` runs only the HDArray programs on a 4-device mesh (halo
by ``ppermute``, B by ``all_gather``) and compares them with numpy and
with the same programs on one device.

A failed check raises: the script exits nonzero and prints no result
line.  Without a TPU it stops at the device check.  The last line of
standard output is one JSON object naming the device:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# The prefill-vs-forward logit check.  Both programs compute in
# bfloat16 (8-bit significand, one rounding moves a value by up to
# 2^-8 of itself).  They run the same ops but in different calls (two
# 64-token chunks, the second reading the first's KV from the cache,
# vs one 128-token pass) and reduce attention over different key
# counts (the 1024-row cache vs the prompt), so roundings can land
# differently and compound over the layers.
# 2^-4 of the largest |logit| is 16 such steps at the top of the
# scale; a wrong layer, mask or position moves logits by O(max|logit|).
LOGIT_TOL = 2.0 ** -4
# The GEMM check, per element against float64: |C - C64| <= GEMM_TOL *
# sqrt(sum_k (a_ik b_kj)^2).  The Pallas GEMM's float32 dot runs as one
# bfloat16 pass on the MXU: each operand is rounded with a relative
# error uniform within +-2^-8, so each product errs by about
# 2^-8 * sqrt(2/3) ~ 0.0032 of itself (one standard deviation), and
# the float32 sum of K of them by about 0.0032 of that root-sum-square.
# 2^-5 is ~10 standard deviations: no honest element of the ~5e5
# sampled reaches it, while a wrong or missing tile errs by
# O(sqrt(K)) times the scale.
GEMM_TOL = 2.0 ** -5
# Serving programs plus the engine's one extra cache copy (admission
# scatters into a new cache while the old one is alive) must fit here;
# the chip has 16 GiB of HBM.
FIT_BYTES = 14 * 2 ** 30


def log(*args) -> None:
    print("[chip_smoke]", *args, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: check failed: {what}")


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# -- device ---------------------------------------------------------------
def device_phase(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"jax {jax.__version__}; devices {devs}")
    log(f"platform {d.platform}, device_kind {d.device_kind}, "
        f"count {len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} devices")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- serving --------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServeSize:
    arch: str = "deepseek-7b"
    reduced: bool = False       # True: the tiny smoke-test widths
    layers: int = 8
    slots: int = 4
    max_seq: int = 1024
    prompt_len: int = 128
    new_tokens: int = 32
    shared_prefix: int = 64
    prefill_chunk: int = 64
    turn_len: int = 100         # first turn: one whole chunk + 36 tokens
    turn_extra: int = 28        # the second turn's new tokens


def make_prompts(size: ServeSize, vocab: int, seed: int):
    """One prompt per slot, prompts 0 and 1 sharing their first
    ``shared_prefix`` tokens; and the first turn of a conversation
    with the tokens its second turn appends after the first's output."""
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, (size.slots, size.prompt_len),
                           dtype=np.int32)
    prompts[1, :size.shared_prefix] = prompts[0, :size.shared_prefix]
    turn = rng.integers(0, vocab, size.turn_len + size.turn_extra,
                        dtype=np.int32)
    return prompts, turn[:size.turn_len], turn[size.turn_len:]


def serve_phase(size: ServeSize, seed: int):
    """Single Engine: fit, streams, repeat, logits.  Returns
    ``(bundle, params, ref)``, the requests and their streams, for the
    pool phase."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.serve import load_engine
    from repro.models.layers import FLASH_MIN_T
    from repro.serve.engine import make_decode_step, make_prefill_step

    t0 = time.perf_counter()
    eng = load_engine(size.arch, reduced=size.reduced, n_layers=size.layers,
                      slots=size.slots, max_seq=size.max_seq,
                      temperature=0.0, prefill_chunk=size.prefill_chunk,
                      seed=seed)
    jax.block_until_ready(eng.params)
    cfg, bundle, params = eng.cfg, eng.bundle, eng.params
    log(f"serve: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} d_head={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab}, layers {cfg.n_layers} of "
        f"{get_config(size.arch).n_layers}; params "
        f"{tree_bytes(params) / 2**30:.3f} GiB float32, cache "
        f"{tree_bytes(eng.cache) / 2**30:.3f} GiB; built in "
        f"{time.perf_counter() - t0:.1f} s")

    # fit: each program the engine runs (prefill runs per chunk) —
    # arguments + outputs + temporaries, as the chip's compiler counts
    # them — plus the engine's extra cache copy
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    chunk = min(size.prompt_len, size.prefill_chunk)
    programs = (
        (f"prefill ({chunk}-token chunks)", make_prefill_step(bundle),
         {"tokens": sds((size.slots, chunk), i32)},
         "flash_attention" if chunk >= FLASH_MIN_T
         else "gqa_attention (dense jnp, full-cache mask)"),
        ("decode", make_decode_step(bundle),
         {"token": sds((size.slots, 1), i32), "pos": sds((size.slots,), i32)},
         "gqa_attention (dense jnp over all max_seq rows)"))
    for name, fn, batch, attn in programs:
        compiled = jax.jit(fn).lower(params, batch, eng.cache).compile()
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes
                + tree_bytes(eng.cache))
        log(f"serve: {name} attention: {attn}; Pallas kernel in program: "
            f"{'tpu_custom_call' in compiled.as_text()}; needs "
            f"{need / 2**30:.3f} GiB (args {m.argument_size_in_bytes} + out "
            f"{m.output_size_in_bytes} + temp {m.temp_size_in_bytes} - alias "
            f"{m.alias_size_in_bytes} + one cache copy) of "
            f"{FIT_BYTES / 2**30:.0f} GiB")
        check(need <= FIT_BYTES, f"{name} program fits {FIT_BYTES} bytes")

    prompts, turn1, extra = make_prompts(size, cfg.vocab, seed)
    t0 = time.perf_counter()
    sids = [eng.add_request(prompts[0])]
    # the first token of prompt 0 came from these chunked prefill
    # calls; their last-position logits are checked against forward
    pf = np.asarray(eng.prefill_logits, np.float64)
    sids += [eng.add_request(p) for p in prompts[1:]]
    for _ in range(size.new_tokens - 1):
        eng.step()
    streams = [eng.finish(s)[size.prompt_len:] for s in sids]
    log(f"serve: {len(prompts)} requests x {size.new_tokens} tokens in "
        f"{time.perf_counter() - t0:.2f} s wall (compiles included)")
    # a conversation: turn 2 is turn 1's prompt and output plus more
    out1 = eng.generate(turn1, size.new_tokens)
    turn2 = np.concatenate([np.asarray(out1, np.int32), extra])
    turns = [out1[len(turn1):],
             eng.generate(turn2, size.new_tokens)[len(turn2):]]
    named = [(f"request {i}", s) for i, s in enumerate(streams)]
    named += [(f"turn {i + 1}", s) for i, s in enumerate(turns)]
    for name, s in named:
        log(f"serve: {name}: {s[:12]} ...")
        check(len(s) == size.new_tokens, f"{name} length {len(s)}")
        check(all(0 <= t < cfg.vocab for t in s),
              f"{name} token ids in [0, {cfg.vocab})")
    again = eng.generate(prompts[2], size.new_tokens)[size.prompt_len:]
    check(again == streams[2], "a repeated request streams the same tokens")
    log("serve: token ids in range; repeated request 2 alone gives the "
        "same stream")
    del eng
    gc.collect()

    # the engine's chunked prefill of prompt 0 against the forward pass
    fw = jax.jit(lambda p, t: bundle.forward(p, {"tokens": t})[0][:, -1])(
        params, jnp.asarray(prompts[:1]))
    fw = np.asarray(fw[0], np.float64)
    scale = np.abs(fw).max()
    err = np.abs(pf - fw).max()
    log(f"serve: engine prefill ({chunk}-token chunks) vs forward, "
        f"last-position logits: max |diff| {err:.6g}, max |logit| "
        f"{scale:.6g}, ratio {err / scale:.6g} (tolerance {LOGIT_TOL}); "
        f"argmax {int(pf.argmax())} vs {int(fw.argmax())}")
    check(np.isfinite(pf).all() and np.isfinite(fw).all(), "finite logits")
    check(err <= LOGIT_TOL * scale, "prefill logits match forward")
    ref = {"prompts": prompts, "streams": streams, "turn1": turn1,
           "extra": extra, "turns": turns}
    return bundle, params, ref


def pool_phase(bundle, params, ref: dict, size: ServeSize,
               seed: int) -> None:
    """ReplicaPool with prefix reuse on the same requests: every stream
    bit-identical to the single engine's, which copied no rows
    (docs/serving.md, the determinism gate)."""
    from repro.serve import ReplicaPool, ServeConfig

    t0 = time.perf_counter()
    pool = ReplicaPool(bundle, params,
                       ServeConfig(max_seq=size.max_seq, slots=size.slots,
                                   prefix_reuse=True,
                                   prefill_chunk=size.prefill_chunk),
                       replicas=2, policy="prefix_aware", seed=seed,
                       checkpoint_interval=size.new_tokens)
    rids = [pool.submit(p, size.new_tokens) for p in ref["prompts"]]
    rid1 = pool.submit(ref["turn1"], size.new_tokens)
    out = pool.run()
    turn2 = np.concatenate([np.asarray(out[rid1], np.int32), ref["extra"]])
    rid2 = pool.submit(turn2, size.new_tokens)
    out = pool.run()
    stats = pool.replica_stats()
    log(f"pool: 2 replicas, {len(rids) + 2} requests in "
        f"{time.perf_counter() - t0:.2f} s wall (compiles included)")
    for rid, st in stats.items():
        log(f"pool: replica {rid}: prefill tokens "
            f"{st['prefill_tokens_computed']}, prefix hits "
            f"{st['prefix_hits']}, prefix tokens reused "
            f"{st['prefix_tokens_reused']}")
    for i, rid in enumerate(rids):
        check(out[rid][size.prompt_len:] == ref["streams"][i],
              f"pool stream {i} bit-identical to the single engine's")
    check(out[rid1][size.turn_len:] == ref["turns"][0]
          and out[rid2][len(turn2):] == ref["turns"][1],
          "pool turns 1 and 2 bit-identical to the single engine's")
    hit = {rid: pool.metrics.requests[rid].prefix_hit_len
           for rid in rids + [rid1, rid2]}
    log(f"pool: prefix rows copied per request {hit}")
    check(hit[rids[1]] == size.shared_prefix,
          "request 1 copied the shared prefix")
    # turn 1 left 131 matching rows (100 prompt + 31 decoded); only the
    # first chunk came from a whole-chunk call
    check(hit[rid2] == size.prefill_chunk,
          "turn 2 copied only turn 1's whole chunk")
    log("pool: every stream bit-identical to the single engine's")


# -- HDArray array programs -------------------------------------------------
def _pipeline_twice(rt, steps, arrays) -> dict:
    """Run the pipeline cold (compiles, uploads), then again: the
    second pass is the steady state, whose transfers are counted."""
    ex = rt.executor
    rt.run_pipeline(steps)
    h2d, d2h = ex.h2d_transfers, ex.d2h_transfers
    rt.run_pipeline(steps)
    st = rt.planner.stats
    compiled, meta = ex.last_program_lowered()
    return {
        "scan_captures": st.scan_captures, "fused_steps": st.fused_steps,
        "steady_h2d": ex.h2d_transfers - h2d,
        "steady_d2h": ex.d2h_transfers - d2h,
        "collectives": {k: v for k, v in ex.collective_counts.items() if v},
        "devices": {a.name: [d.id for d in ex.shard_devices(a)]
                    for a in arrays},
        "platforms": {d.platform for a in arrays
                      for d in ex.shard_devices(a)},
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "last_program": meta}


def jacobi_program(nproc: int, n: int, sweeps: int, seed: int):
    """Ping-pong Jacobi (A <- avg4(B), B <- avg4(A)) with the Pallas
    stencil, ``sweeps`` steps per pipeline, run twice.  Returns the
    final B and the run's counters."""
    from repro.core import AccessSpec, Box, HDArrayRuntime, IDENTITY_2D
    from repro.kernels.hd import make_jacobi_kernel

    x0 = np.random.default_rng(seed).standard_normal((n, n),
                                                      dtype=np.float32)
    rt = HDArrayRuntime(nproc, backend="jax")
    pd = rt.partition_row((n, n))
    pw = rt.partition_row((n, n), region=Box.make((1, n - 1), (1, n - 1)))
    ha, hb = rt.create("A", (n, n)), rt.create("B", (n, n))
    rt.write(ha, x0, pd)
    rt.write(hb, x0, pd)
    fp = AccessSpec.of((0, -1), (0, 1), (-1, 0), (1, 0), (0, 0))
    k_ab = make_jacobi_kernel("B", "A", impl="pallas")
    k_ba = make_jacobi_kernel("A", "B", impl="pallas")
    steps = [dict(kernel_name="jac_ab", part_id=pw, kernel=k_ab,
                  arrays=[ha, hb], uses={"B": fp}, defs={"A": IDENTITY_2D})
             if i % 2 == 0 else
             dict(kernel_name="jac_ba", part_id=pw, kernel=k_ba,
                  arrays=[ha, hb], uses={"A": fp}, defs={"B": IDENTITY_2D})
             for i in range(sweeps)]
    info = _pipeline_twice(rt, steps, [ha, hb])
    out = rt.read_coherent(hb)
    rt.close()
    return x0, out, info


def jacobi_numpy(x: np.ndarray, sweeps: int) -> np.ndarray:
    """The reference: float32, the kernel's summation order."""
    cur, nxt = x.copy(), x.copy()
    for _ in range(sweeps):
        mid = nxt[1:-1, 1:-1]
        np.add(cur[1:-1, :-2], cur[1:-1, 2:], out=mid)
        mid += cur[:-2, 1:-1]
        mid += cur[2:, 1:-1]
        mid *= np.float32(0.25)
        cur, nxt = nxt, cur
    return cur


def gemm_program(nproc: int, n: int, steps_n: int, seed: int):
    """Row-band GEMM (A ROW_ALL, B COL_ALL, C identity) with the Pallas
    GEMM, ``steps_n`` steps per pipeline, run twice."""
    from repro.core import COL_ALL, HDArrayRuntime, IDENTITY_2D, ROW_ALL
    from repro.kernels.hd import make_gemm_kernel

    rng = np.random.default_rng(seed + 1)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)
    rt = HDArrayRuntime(nproc, backend="jax")
    part = rt.partition_row((n, n))
    ha, hb, hc = (rt.create(s, (n, n)) for s in "abc")
    rt.write(ha, a, part)
    rt.write(hb, b, part)
    rt.write(hc, np.zeros((n, n), np.float32), part)
    mm = make_gemm_kernel("a", "b", "c", impl="pallas")
    steps = [dict(kernel_name="gemm", part_id=part, kernel=mm,
                  arrays=[ha, hb, hc], uses={"a": ROW_ALL, "b": COL_ALL},
                  defs={"c": IDENTITY_2D})] * steps_n
    info = _pipeline_twice(rt, steps, [ha, hb, hc])
    out = rt.read(hc, part)
    rt.close()
    return a, b, out, info


def gemm_sample_rows(n: int, nproc_max: int, seed: int) -> np.ndarray:
    """Random rows plus the first and last row of every band."""
    bounds = {0, n - 1}
    for p in range(1, nproc_max):
        bounds |= {p * n // nproc_max - 1, p * n // nproc_max}
    rnd = np.random.default_rng(seed + 2).choice(n, 48, replace=False)
    return np.array(sorted(bounds | set(rnd.tolist())))


def gemm_errors(a, b, cs, rows) -> list:
    """For each C in `cs`: max over the sampled elements of |C - C64|
    / rss(products), where C64 is the float64 product.  The first C is
    also compared with each other one on the same scale (last entry)."""
    a64 = a[rows].astype(np.float64)
    b64 = b.astype(np.float64)
    ref = a64 @ b64
    rss = np.sqrt((a64 * a64) @ (b64 * b64))
    errs = [float((np.abs(c[rows] - ref) / rss).max()) for c in cs]
    errs += [float((np.abs(c[rows] - cs[0][rows]) / rss).max())
             for c in cs[1:]]
    return errs


def log_info(tag: str, info: dict) -> None:
    log(f"{tag}: scan_captures {info['scan_captures']}, fused_steps "
        f"{info['fused_steps']}, steady h2d {info['steady_h2d']} d2h "
        f"{info['steady_d2h']}, collectives {info['collectives']}, "
        f"tpu_custom_call {info['tpu_custom_call']} (last program "
        f"{info['last_program']}), shard devices {info['devices']}")


def check_info(tag: str, info: dict, nproc: int) -> None:
    check(info["scan_captures"] >= 1, f"{tag}: a steady cycle was captured")
    check(info["steady_h2d"] == 0 and info["steady_d2h"] == 0,
          f"{tag}: no host<->device transfer in the steady pass")
    check(info["tpu_custom_call"], f"{tag}: the Pallas kernel compiled "
          "into the step program (tpu_custom_call)")
    check(info["platforms"] == {"tpu"}, f"{tag}: shards on TPU devices")
    for name, ids in info["devices"].items():
        check(len(set(ids)) == nproc,
              f"{tag}: {name} shards on {nproc} distinct devices ({ids})")


@dataclasses.dataclass(frozen=True)
class ArraySize:
    jacobi_n: int = 16384       # 1 GiB per float32 array
    sweeps: int = 8             # per pipeline pass; two passes run
    gemm_n: int = 8192
    gemm_steps: int = 6


def hdarray_phase(size: ArraySize, nprocs, seed: int) -> None:
    """Jacobi and GEMM on HDArrayRuntime(p, backend="jax") for each p in
    `nprocs`, every result against numpy, and against each other."""
    t0 = time.perf_counter()
    ref = None
    jac = {}
    for p in nprocs:
        x0, out, info = jacobi_program(p, size.jacobi_n, size.sweeps, seed)
        log_info(f"jacobi p={p} n={size.jacobi_n}", info)
        check_info(f"jacobi p={p}", info, p)
        if p > 1:
            check(info["collectives"].get("ppermute", 0) > 0,
                  f"jacobi p={p}: halo exchanged by ppermute")
        if ref is None:
            ref = jacobi_numpy(x0, 2 * size.sweeps)
        del x0
        check(np.array_equal(out, ref), f"jacobi p={p}: every element "
              "equals the numpy reference")
        log(f"jacobi p={p}: all {out.size} elements equal numpy after "
            f"{2 * size.sweeps} sweeps (tolerance 0: same float32 ops in "
            f"the same order)")
        jac[p] = out
    if len(jac) > 1:
        check(all(np.array_equal(v, jac[nprocs[0]]) for v in jac.values()),
              "jacobi: the mesh result equals the one-device result")
    del ref, jac
    gc.collect()

    rows = gemm_sample_rows(size.gemm_n, max(nprocs), seed)
    outs = []
    for p in nprocs:
        a, b, out, info = gemm_program(p, size.gemm_n, size.gemm_steps, seed)
        log_info(f"gemm p={p} n={size.gemm_n}", info)
        check_info(f"gemm p={p}", info, p)
        if p > 1:
            check(info["collectives"].get("all_gather", 0) > 0,
                  f"gemm p={p}: B gathered by all_gather")
        outs.append(out)
    errs = gemm_errors(a, b, outs, rows)
    for p, err in zip(nprocs, errs):
        log(f"gemm p={p}: {len(rows)} sampled rows vs float64: max "
            f"|C - C64| / rss {err:.6g} (tolerance {GEMM_TOL})")
        check(err <= GEMM_TOL, f"gemm p={p}: sampled rows match float64")
    for p, out, err in zip(nprocs[1:], outs[1:], errs[len(nprocs):]):
        log(f"gemm p={p} vs p={nprocs[0]}: bit-identical "
            f"{np.array_equal(out, outs[0])}, sampled max |diff| / rss "
            f"{err:.6g} (tolerance {GEMM_TOL})")
        check(err <= GEMM_TOL, f"gemm p={p} matches the p={nprocs[0]} "
              "result")
    log(f"hdarray: done in {time.perf_counter() - t0:.1f} s wall")


# -- main -------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the HDArray programs on a 4-chip "
                         "mesh, against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    device = device_phase(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        hdarray_phase(ArraySize(), (1, 4), args.seed)
    else:
        size = ServeSize()
        bundle, params, ref = serve_phase(size, args.seed)
        pool_phase(bundle, params, ref, size, args.seed)
        del bundle, params
        gc.collect()
        hdarray_phase(ArraySize(), (1,), args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
