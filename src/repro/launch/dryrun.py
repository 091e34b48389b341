import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the first lines: jax locks the device count at first init.
# The 512 placeholder host devices exist ONLY for the dry-run — smoke
# tests and benches see the real single CPU device.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape
x mesh) cell against the production mesh, and derive the roofline terms
from the compiled artifact.

For each cell this:
  1. builds the arch at its EXACT assigned config (no allocation —
     ShapeDtypeStruct stand-ins from cfg.input_specs),
  2. maps every param's logical axes to mesh axes with the HDArray
     rules table (train/sharding.py) — the paper's partition choice,
  3. jit-lowers train_step / prefill / decode with explicit in/out
     shardings, compiles, prints memory_analysis + cost_analysis,
  4. parses the optimized HLO for collective bytes and writes the
     roofline report JSON (results/dryrun/<arch>__<shape>__<mesh>.json).

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --sweep --mesh both        # all cells
  python -m repro.launch.dryrun --list                     # show cells
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_configs, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.optim import adamw
from repro.roofline import analysis as RL
from repro.train import sharding as SH
from repro.train.step import TrainConfig, make_train_step

_DEFAULT_RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..",
                                    "..", "results", "dryrun")


def results_dir() -> str:
    """Where result records live.  REPRO_RESULTS_DIR (resolved at call
    time, so monkeypatched env vars work) lets CI / tests regenerate
    cells without rewriting the committed baselines in results/dryrun."""
    return os.environ.get("REPRO_RESULTS_DIR") or _DEFAULT_RESULTS_DIR

# Per-arch scale knobs (microbatches bound saved-activation HBM; moment
# dtype bounds optimizer-state HBM).  These are the BASELINE settings —
# §Perf hillclimbs adjust them per cell.
TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    # microbatches sized so saved activations + the (unfused) CE logits
    # (B_local x seq x vocab x 4B) stay near the 16 GB/chip budget — the
    # fused-CE §Perf iteration relaxes these again.
    "deepseek-v3-671b": dict(microbatches=16, param_dtype="bf16",
                             accum_dtype="bf16", moment_dtype="bf16"),
    "mistral-large-123b": dict(microbatches=16, moment_dtype="bf16"),
    "qwen3-moe-30b-a3b": dict(microbatches=16),
    "deepseek-7b": dict(microbatches=8),
    "yi-9b": dict(microbatches=8),
    "gemma2-9b": dict(microbatches=16),
    "llama-3.2-vision-11b": dict(microbatches=8),
    "recurrentgemma-2b": dict(microbatches=16),
    "xlstm-125m": dict(microbatches=8),
    "whisper-base": dict(microbatches=8),
}

RULES = {"baseline": SH.baseline_rules, "zero3": SH.zero3_rules,
         "serve": SH.serve_rules}


def _split_overrides(ov: Dict[str, Any]) -> Tuple[TrainConfig, str]:
    ov = dict(ov)
    moment = ov.pop("moment_dtype", "fp32")
    return TrainConfig(**ov), moment


def shapes_and_specs(bundle):
    """eval_shape init -> (params ShapeDtypeStruct tree, logical specs).
    Specs are static strings built at trace time — captured by side
    effect so eval_shape never sees non-array leaves."""
    cell = {}

    def only_params(key):
        p, s = bundle.init(key)
        cell["specs"] = s
        return p

    params_shape = jax.eval_shape(only_params, jax.random.PRNGKey(0))
    return params_shape, cell["specs"]


def _cast_shapes(tree, dtype):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if x.dtype == jnp.float32 else x.dtype), tree)


def _spec_tree_is_leaf(x):
    return isinstance(x, tuple) and all(isinstance(s, str) for s in x)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rules_name: str = "baseline",
               train_overrides: Optional[Dict[str, Any]] = None,
               verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one cell; returns the result record."""
    t_start = time.time()
    cfg = get_config(arch)
    shape_cell = SHAPES[shape_name]
    ok, why = cfg.supports_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "rules": rules_name, "status": "skip", "why": why,
    }
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    # §Perf iteration 5: inference cells use TP-only rules (FSDP on a
    # contracting dim turns serving matmuls into activation all-reduces).
    # §Perf iteration 6: UNLESS the TP-only param bytes per chip exceed
    # half the HBM (dsv3 84 GiB, mistral 15 GiB) — those keep FSDP
    # (ZeRO-inference: per-layer weight gathers instead of resident).
    if rules_name == "baseline" and shape_cell.kind != "train":
        tp_bytes_per_dev = cfg.param_count() * 2 / mesh.shape.get("model", 1)
        if tp_bytes_per_dev < 8 * 2**30:
            rules_name = "serve"
    rules = RULES[rules_name](multi_pod)
    rec["rules"] = rules_name
    bundle = build(cfg)
    params_shape, specs = shapes_and_specs(bundle)
    batch = cfg.input_specs(shape_name)
    batch_sh = SH.batch_shardings(batch, mesh, rules)
    ov = dict(TRAIN_OVERRIDES.get(arch, {}))
    if train_overrides:
        ov.update(train_overrides)
    tcfg, moment_dtype = _split_overrides(ov)
    # §Perf iteration 1: per-microbatch batch rows must still divide the
    # batch shards (pod x data), else the microbatch scan replicates the
    # batch over 'pod' (observed: gemma2 multi-pod useful 0.72 -> 0.24).
    n_batch = 1
    for a in rules.batch_axes:
        n_batch *= mesh.shape.get(a, 1)
    mb = tcfg.microbatches
    while mb > 1 and (shape_cell.global_batch // mb) % n_batch:
        mb //= 2
    if mb != tcfg.microbatches:
        tcfg = dataclasses.replace(tcfg, microbatches=mb)
    rec["train_cfg"] = dataclasses.asdict(tcfg)
    rec["moment_dtype"] = moment_dtype

    with mesh, jax.sharding.set_mesh(mesh):
        if shape_cell.kind == "train":
            if tcfg.param_dtype == "bf16":
                params_shape = _cast_shapes(params_shape, jnp.bfloat16)
            param_sh = SH.param_shardings(specs, params_shape, mesh, rules)
            ocfg = adamw.AdamWConfig(moment_dtype=moment_dtype)
            opt_shape = jax.eval_shape(
                lambda p: adamw.init_opt_state(ocfg, p), params_shape)
            opt_sh = adamw.OptState(
                step=NamedSharding(mesh, P()), mu=param_sh, nu=param_sh)
            step = make_train_step(bundle, ocfg, tcfg)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P())),
                donate_argnums=(0, 1))
            t0 = time.time()
            lowered = jitted.lower(params_shape, opt_shape, batch)
        else:
            params_shape = _cast_shapes(params_shape, jnp.bfloat16)
            param_sh = SH.param_shardings(specs, params_shape, mesh, rules)
            cache_shape = jax.eval_shape(
                lambda: bundle.init_cache(shape_cell.global_batch,
                                          shape_cell.seq_len))
            cache_sh = SH.cache_shardings(cache_shape, mesh, rules,
                                          batch_size=shape_cell.global_batch)
            fn = (bundle.prefill if shape_cell.kind == "prefill"
                  else bundle.decode)
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, batch_sh, cache_sh),
                out_shardings=(NamedSharding(mesh, P()), cache_sh),
                donate_argnums=(2,))
            t0 = time.time()
            lowered = jitted.lower(params_shape, batch, cache_shape)

        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    # ---- memory / cost analyses (assignment step 3) -------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if verbose:
            print(ma)
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = int(getattr(ma, f, 0))
        mem["total_hbm_bytes"] = (mem["temp_size_in_bytes"]
                                  + mem["argument_size_in_bytes"]
                                  + mem["output_size_in_bytes"]
                                  - mem["alias_size_in_bytes"])
    except Exception as e:  # pragma: no cover
        mem["error"] = repr(e)
    rec["memory"] = mem

    ca = compiled.cost_analysis()
    rec["cost"] = {k: float(v) for k, v in ca.items()
                   if isinstance(v, (int, float)) and
                   k in ("flops", "bytes accessed", "transcendentals",
                         "utilization operand 0 {}", "optimal_seconds")}
    if verbose:
        print({k: rec["cost"].get(k) for k in ("flops", "bytes accessed")})

    # ---- roofline ------------------------------------------------------
    rep = RL.analyze(compiled, arch=arch, shape=shape_name,
                     mesh_name=mesh_name, n_chips=n_chips,
                     model_flops_total=RL.model_flops(cfg, shape_cell))
    rec["roofline"] = rep.to_dict()
    rec["collective_ops"] = RL.count_collectives(compiled.as_text())
    rec["status"] = "ok"
    rec["total_s"] = round(time.time() - t_start, 2)
    return rec


def _result_path(arch, shape, mesh_name, rules):
    sfx = "" if rules == "baseline" else f"__{rules}"
    return os.path.join(results_dir(),
                        f"{arch}__{shape}__{mesh_name}{sfx}.json")


def run_cell(arch, shape, multi_pod, rules="baseline", force=False,
             train_overrides=None) -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    path = _result_path(arch, shape, mesh_name, rules)
    prior = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                prior = json.load(f)
        except ValueError:
            prior = None
        if not isinstance(prior, dict):
            prior = None  # corrupt/garbled file: treat as absent
        # Error records are environment failures, not results — never a
        # cache hit, or one bad run poisons every later sweep.
        if not force and prior is not None and prior.get("status") != "error":
            return prior
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        rec = lower_cell(arch, shape, multi_pod, rules,
                         train_overrides=train_overrides)
    except Exception as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "rules": rules, "status": "error", "error": repr(e),
               "trace": traceback.format_exc()[-4000:]}
    if (rec["status"] == "error" and prior is not None
            and prior.get("status") != "error"):
        # Keep the last good record on disk rather than clobbering it;
        # a stale error record is still refreshed with the new failure.
        return rec
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
        f.write("\n")
    return rec


def all_cells():
    out = []
    for arch, cfg in sorted(all_configs().items()):
        for shape in SHAPES:
            out.append((arch, shape, cfg.supports_shape(shape)[0]))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--rules", default="baseline", choices=sorted(RULES))
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shape, ok in all_cells():
            print(f"{arch:24s} {shape:12s} {'run' if ok else 'SKIP'}")
        return

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.sweep:
        cells = [(a, s) for a, s, ok in all_cells() if ok
                 if (args.arch is None or a == args.arch)
                 if (args.shape is None or s == args.shape)]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --sweep)"
        cells = [(args.arch, args.shape)]

    t0 = time.time()
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            rec = run_cell(arch, shape, mp, args.rules, force=args.force)
            r = rec.get("roofline", {})
            print(f"[{time.time()-t0:7.1f}s] {arch:24s} {shape:12s} "
                  f"{mesh_name:10s} {rec['status']:5s} "
                  f"compile={rec.get('compile_s', '-')}s "
                  f"bottleneck={r.get('bottleneck', '-')} "
                  f"roofline={r.get('roofline_fraction', 0):.3f}"
                  + (f" ERR={rec.get('error', '')[:120]}"
                     if rec["status"] == "error" else ""),
                  flush=True)


if __name__ == "__main__":
    main()
