"""Mixture-of-Experts FFN (deepseek-v3, qwen3-moe).

Two dispatch implementations, selected by `impl`:

  * ``"sort"`` (baseline): top-k routing + argsort-based grouping into
    (E, C) capacity slots, batched expert matmul, scatter back.  FLOPs
    scale with ACTIVE experts only (capacity_factor overhead); under
    pjit the expert dim shards over the 'model'/'expert' mesh axis and
    XLA inserts the collectives.
  * ``"a2a"`` (beyond-paper optimization, §Perf): the same computation
    expressed with an explicit shard_map all-to-all — the lowering the
    HDArray planner picks once it classifies the dispatch pattern as
    CommKind.ALL_TO_ALL.  (Hooked up in train/sharding.py.)

Router: softmax over experts, top-k, renormalized weights; optional
shared experts added unconditionally (deepseek-v3).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp



def moe_params(key, d_model: int, mo, n_layers: int) -> Tuple[Dict, Dict]:
    E, F = mo.num_experts, mo.d_expert_ff
    ks = jax.random.split(key, 5)
    L = n_layers
    p = {
        "router": jax.random.normal(ks[0], (L, d_model, E), jnp.float32) * 0.02,
        "w_gate": jax.random.normal(ks[1], (L, E, d_model, F), jnp.float32) / math.sqrt(d_model),
        "w_up": jax.random.normal(ks[2], (L, E, d_model, F), jnp.float32) / math.sqrt(d_model),
        "w_down": jax.random.normal(ks[3], (L, E, F, d_model), jnp.float32) / math.sqrt(F),
    }
    spec = {
        "router": ("layers", "embed", "experts_r"),
        "w_gate": ("layers", "experts", "embed", "expert_mlp"),
        "w_up": ("layers", "experts", "embed", "expert_mlp"),
        "w_down": ("layers", "experts", "expert_mlp", "embed"),
    }
    if mo.n_shared:
        Fs = mo.d_shared_ff or F
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(kss[0], (L, d_model, mo.n_shared * Fs), jnp.float32) / math.sqrt(d_model),
            "w_up": jax.random.normal(kss[1], (L, d_model, mo.n_shared * Fs), jnp.float32) / math.sqrt(d_model),
            "w_down": jax.random.normal(kss[2], (L, mo.n_shared * Fs, d_model), jnp.float32) / math.sqrt(Fs),
        }
        spec["shared"] = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    return p, spec


def _route(router_w, x, top_k: int):
    """x: (N, D) -> (weights (N, k), ids (N, k), aux_loss)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    E = logits.shape[-1]
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / ids.size
    aux = E * jnp.sum(me * ce)
    return w, ids, aux


def _dispatch_compute_combine(xf, w, ids, wg, wu, wd, *, n_experts: int,
                              e_base, top_k: int, capacity: int):
    """Core dispatch for experts [e_base, e_base + n_experts) over local
    tokens xf (N, D).  Tokens routed to other experts go to the trash
    slot.  Returns the (N, D) PARTIAL output (only this expert range)."""
    N, D = xf.shape
    cdt = xf.dtype
    E, C = n_experts, capacity
    k = top_k
    flat_e = ids.reshape(-1) - e_base                     # local expert id
    in_range = (flat_e >= 0) & (flat_e < E)
    flat_e = jnp.where(in_range, flat_e, E)               # E = trash group
    flat_t = jnp.repeat(jnp.arange(N), k)
    flat_w = w.reshape(-1).astype(cdt)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    pos_in_e = jnp.arange(N * k) - jnp.searchsorted(se, se, side="left")
    keep = (pos_in_e < C) & (se < E)                      # capacity drop
    slot = jnp.where(keep, se * C + pos_in_e, E * C)
    xs = jnp.zeros((E * C + 1, D), cdt).at[slot].set(xf[st].astype(cdt))
    ws = jnp.zeros((E * C + 1,), cdt).at[slot].set(jnp.where(keep, sw, 0))
    ts = jnp.full((E * C + 1,), N, jnp.int32).at[slot].set(
        jnp.where(keep, st, N))
    xe = xs[:-1].reshape(E, C, D)
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(cdt))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(cdt))
    yw = y.reshape(E * C, D) * ws[:-1][:, None]
    return jnp.zeros((N + 1, D), cdt).at[ts[:-1]].add(yw)[:-1]


def _shared_ffn(p, x, cdt):
    sp = p["shared"]
    g = x @ sp["w_gate"].astype(cdt)
    u = x @ sp["w_up"].astype(cdt)
    return (jax.nn.silu(g) * u) @ sp["w_down"].astype(cdt)


def moe_ffn(p, x, mo, *, impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out (B, T, D), aux_loss).  Unstacked layer params.

    impl='sort'  — single logical device: sort dispatch over all E.
    impl='ep'    — expert parallelism via shard_map: experts live on the
                   'model' axis; every model column redundantly routes
                   the (model-replicated) activations, LOCALLY gathers
                   only its own experts' slots, and one psum combines
                   partial outputs.  Removes the data-dependent
                   gather/scatter over sharded buffers that GSPMD can
                   only lower by replicating + all-reducing (§Perf
                   iteration 3: dsv3/qwen3 train memory & collectives).
    impl='auto'  — 'ep' when a mesh with a divisible 'model' axis is in
                   context (dry-run/launchers), else 'sort' (CPU tests).
    """
    if impl == "auto":
        m = jax.sharding.get_abstract_mesh()
        ok = (m is not None and "model" in m.shape
              and mo.num_experts % m.shape["model"] == 0)
        impl = "ep" if ok else "sort"
    if impl == "ep":
        return _moe_ffn_ep(p, x, mo)
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    w, ids, aux = _route(p["router"], xf, mo.top_k)
    C = max(1, int(mo.capacity_factor * B * T * mo.top_k / mo.num_experts))
    out = _dispatch_compute_combine(
        xf, w, ids, p["w_gate"], p["w_up"], p["w_down"],
        n_experts=mo.num_experts, e_base=0, top_k=mo.top_k, capacity=C)
    out = out.reshape(B, T, D)
    if "shared" in p:
        out = out + _shared_ffn(p, x, x.dtype)
    return out, aux


def _moe_ffn_ep(p, x, mo) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel path (see moe_ffn docstring)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    nm = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    B, T, D = x.shape
    if nb > 1 and B % nb != 0:        # non-divisible decode batch
        batch_axes, nb = (), 1
    E_loc = mo.num_experts // nm
    N_loc = (B // max(nb, 1)) * T
    C = max(1, int(mo.capacity_factor * N_loc * mo.top_k / mo.num_experts))
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)

    has_shared = "shared" in p
    shared_p = p.get("shared", {})

    def body(xl, router, wg, wu, wd, sg, su, sd):
        # xl (B_loc, T, D) — replicated over 'model'; wg (E_loc, D, F)
        Bl = xl.shape[0]
        xf = xl.reshape(Bl * T, D)
        w, ids, aux = _route(router, xf, mo.top_k)
        j = jax.lax.axis_index("model")
        out = _dispatch_compute_combine(
            xf, w, ids, wg, wu, wd, n_experts=E_loc, e_base=j * E_loc,
            top_k=mo.top_k, capacity=C).reshape(Bl, T, D)
        if has_shared:
            # shared expert F dim is model-sharded: partial out too
            cdt = xl.dtype
            g = xl @ sg.astype(cdt)
            u = xl @ su.astype(cdt)
            out = out + (jax.nn.silu(g) * u) @ sd.astype(cdt)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    in_specs = (P(bspec), P(), P("model"), P("model"), P("model"),
                P(None, "model"), P(None, "model"), P("model", None))
    out_specs = (P(bspec), P())
    args = (x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            shared_p.get("w_gate", jnp.zeros((D, nm), x.dtype)),
            shared_p.get("w_up", jnp.zeros((D, nm), x.dtype)),
            shared_p.get("w_down", jnp.zeros((nm, D), x.dtype)))
    out, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)
    return out, aux
