"""Serving driver: batched prefill/decode with the slot Engine.

On the production mesh the SAME prefill/decode functions lower with the
shardings of launch/dryrun.py (the decode_* cells); here they run for
real on local devices with a reduced config — examples/serve_lm.py uses
this — or, with ``--full --layers N``, at published widths cut to N
layers (deepseek-7b fits one 16 GiB v5e chip at 8 of its 30).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build
from repro.serve import Engine, ServeConfig


def load_engine(arch: str, *, reduced: bool = True,
                n_layers: Optional[int] = None, slots: int = 4,
                max_seq: int = 256, temperature: float = 0.0,
                prefill_chunk: Optional[int] = None,
                seed: int = 0) -> Engine:
    """Engine over `arch` with params from `seed`.  ``reduced`` swaps
    in the tiny smoke-test widths; ``n_layers`` cuts the depth and
    keeps the widths, so published widths fit one chip's memory.
    ``prefill_chunk`` as in :class:`ServeConfig`."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if n_layers is not None:
        if not 1 <= n_layers <= cfg.n_layers:
            raise ValueError(f"n_layers={n_layers} outside [1, "
                             f"{cfg.n_layers}] for {arch}")
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    bundle = build(cfg)
    # one jitted init: built op by op, published widths spend over a
    # minute compiling the initializers one at a time on a TPU
    params = jax.jit(lambda key: bundle.init(key)[0])(
        jax.random.PRNGKey(seed))
    return Engine(bundle, params,
                  ServeConfig(max_seq=max_seq, slots=slots,
                              temperature=temperature,
                              prefill_chunk=prefill_chunk), seed=seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--full", action="store_true",
                    help="published widths (default: reduced)")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep only the first N layers")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    eng = load_engine(args.arch, reduced=not args.full,
                      n_layers=args.layers, slots=args.slots,
                      max_seq=args.max_seq, temperature=args.temperature)
    rng = np.random.default_rng(0)
    cfg = eng.cfg
    extra = {}
    if cfg.encdec is not None:
        extra["frames"] = np.asarray(
            rng.standard_normal((args.slots, cfg.encdec.n_frames,
                                 cfg.d_model)), np.float32)
    if cfg.vision is not None:
        extra["image_embeds"] = np.asarray(
            rng.standard_normal((args.slots, cfg.vision.n_image_tokens,
                                 cfg.vision.d_vision)), np.float32)

    t0 = time.time()
    n_tok = 0
    for r in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, args.prompt_len)
        out = eng.generate(prompt, args.tokens, extra_inputs=extra or None)
        n_tok += args.tokens
        print(f"[serve] req {r}: prompt {args.prompt_len} -> "
              f"{out[args.prompt_len:][:16]} ...")
    dt = time.time() - t0
    print(f"[serve] {args.requests} requests, {n_tok} tokens "
          f"in {dt:.2f}s ({n_tok/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
