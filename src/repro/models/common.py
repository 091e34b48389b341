"""Shared model components (pure JAX, pytree params).

Every parameter is created through :func:`param`, which returns the
array AND records a tuple of *logical axis names* (('vocab','embed'),
('layers','embed','q_heads','head_dim'), ...).  The sharding layer
(`repro.train.sharding`) maps logical names -> mesh axes with a rules
table — the HDArray planner's partition choice expressed MaxText-style,
so a hillclimb step is a one-line rule change.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class ParamCollector:
    """Collects params + logical specs during init."""

    def __init__(self, key: jax.Array, dtype=jnp.float32):
        self._key = key
        self.dtype = dtype
        self.specs: Dict[str, Any] = {}

    def split(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def param(self, shape: Sequence[int], logical: Tuple[str, ...],
              init: str = "normal", scale: Optional[float] = None) -> jax.Array:
        assert len(shape) == len(logical), (shape, logical)
        if init == "zeros":
            return jnp.zeros(shape, self.dtype), logical
        if init == "ones":
            return jnp.ones(shape, self.dtype), logical
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(max(1, fan_in))
        return (jax.random.normal(self.split(), shape, self.dtype) * s), logical


def tree_split_specs(tree_with_specs):
    """Split a pytree whose leaves are (array, logical-tuple) pairs."""
    params = jax.tree.map(lambda x: x[0], tree_with_specs,
                          is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                          and isinstance(x[1], tuple))
    specs = jax.tree.map(lambda x: x[1], tree_with_specs,
                         is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                         and isinstance(x[1], tuple))
    return params, specs


# ----------------------------------------------------------------------
# sharding hints
# ----------------------------------------------------------------------
BATCH_AXES = ("pod", "data")   # activation batch dims, outer->inner


def constrain_dims(x, spec_map):
    """Constrain chosen dims of `x` to mesh axes, leaving the rest
    unconstrained.  `spec_map`: {dim: axis-or-tuple}; for a tuple of
    candidate dims as key-alternatives use `constrain_first`.  Dims that
    don't divide the axis product are skipped.  No-op when no mesh is in
    context (CPU unit tests) — dry-run/launchers set one via
    jax.sharding.set_mesh.

    This pins activation shardings inside blockwise attention: GSPMD
    loses batch/head sharding through the blocked reshape + scan carries
    and silently REPLICATES the T·S einsums — a 16x attention-FLOP
    regression the roofline walker caught (EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P


    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.shape:
        return x
    spec = [P.UNCONSTRAINED] * x.ndim
    hit = False
    for d, ax in spec_map.items():
        axs = (ax,) if isinstance(ax, str) else tuple(ax)
        axs = tuple(a for a in axs if a in m.shape)
        n = 1
        for a in axs:
            n *= m.shape[a]
        if axs and n > 1 and x.shape[d] >= n and x.shape[d] % n == 0:
            spec[d] = axs if len(axs) > 1 else axs[0]
            hit = True
    if not hit:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def sharded_batch_update(cache, new, pos):
    """Per-sequence cache write: cache[b, pos[b]:pos[b]+t] = new[b].

    Under a mesh this wraps the vmapped dynamic_update_slice in a
    shard_map so the write is LOCAL per shard — GSPMD lowers the ragged
    (per-batch-position) scatter with an 'involuntary full
    rematerialization' that replicates the whole KV cache (20+ GiB temp
    per decode step on the 32k cells; §Perf iteration 7)."""
    from jax.sharding import PartitionSpec as P


    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (p,) + (0,) * (c.ndim - 1))

    def local(c, n, p):
        return jax.vmap(upd)(c, n, p)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return local(cache, new, pos)
    baxes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    b = (baxes if len(baxes) > 1 else baxes[0]) \
        if nb > 1 and cache.shape[0] % nb == 0 else None
    nm = mesh.shape.get("model", 1)
    last = ("model" if nm > 1 and cache.shape[-1] % nm == 0
            and cache.shape[-1] >= nm else None)
    spec_c = P(b, *([None] * (cache.ndim - 2)), last)
    spec_n = P(b, *([None] * (new.ndim - 2)), last)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_c, spec_n, P(b)),
                         out_specs=spec_c, check_vma=False)(cache, new, pos)


def constrain_attention_blocks(x, batch_dim, head_dims):
    """Batch dim over the data axes; first divisible head dim over
    'model'."""
    m = {batch_dim: BATCH_AXES}
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and "model" in mesh.shape:
        n = mesh.shape["model"]
        for d in head_dims:
            if x.shape[d] >= n and x.shape[d] % n == 0:
                m[d] = "model"
                break
    return constrain_dims(x, m)


# ----------------------------------------------------------------------
# numerics
# ----------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x, cap: Optional[float]):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


def rope(x, positions, base: float = 10000.0, scale: float = 1.0):
    """Rotary embedding over the last dim.  x: (..., T, H, Dh)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq * scale  # (..., T, half)
    ang = ang[..., None, :]                                        # (..., T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def make_causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """(q_len, kv_len) boolean mask.  q_offset = absolute pos of query 0."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    k_pos = jnp.arange(kv_len)[None, :]
    return k_pos <= q_pos


def make_local_mask(q_len: int, kv_len: int, q_offset, window: int) -> jax.Array:
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    k_pos = jnp.arange(kv_len)[None, :]
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def gqa_attention(q, k, v, mask, attn_softcap: Optional[float] = None,
                  scale: Optional[float] = None):
    """Grouped-query attention.

    q: (B, Tq, Hq, Dh); k,v: (B, Tk, Hkv, Dh); mask: (Tq, Tk) or
    (B, Tq, Tk) boolean.  Returns (B, Tq, Hq, Dh).
    """
    B, Tq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Tq, Hkv, groups, Dh)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg * scale, k)
    logits = softcap(logits, attn_softcap)
    if mask.ndim == 2:
        mask_b = mask[None, None, None]
    else:
        mask_b = mask[:, None, None]
    logits = jnp.where(mask_b, logits.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, Tq, Hq, Dh)


def gated_mlp(x, w_gate, w_up, w_down, act: str = "silu"):
    g = x @ w_gate
    u = x @ w_up
    a = jax.nn.gelu(g, approximate=True) if act == "gelu" else jax.nn.silu(g)
    return (a * u) @ w_down


def fused_cross_entropy(x, final_norm, out_emb, labels, mask=None,
                        final_softcap: float = 0.0, chunk: int = 512):
    """Head matmul + CE fused over SEQUENCE CHUNKS (lax.scan +
    checkpoint): never materializes the (B, S, V) logits — the single
    biggest activation of every high-vocab train step (§Perf it. 8).
    Numerically identical to head()+cross_entropy_loss (same fp32 math
    per chunk)."""
    B, S, D = x.shape
    c = min(chunk, S)
    nc = -(-S // c)
    Sp = nc * c
    if Sp != S:
        pad = [(0, 0), (0, Sp - S), (0, 0)]
        x = jnp.pad(x, pad)
        labels = jnp.pad(labels, [(0, 0), (0, Sp - S)])
        mask = jnp.pad(mask if mask is not None
                       else jnp.ones((B, S), jnp.float32),
                       [(0, 0), (0, Sp - S)])
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    xs = x.reshape(B, nc, c, D).swapaxes(0, 1)
    ls = labels.reshape(B, nc, c).swapaxes(0, 1)
    ms = mask.reshape(B, nc, c).swapaxes(0, 1)
    w = out_emb.astype(x.dtype)

    def body(acc, args):
        xc, lc, mc = args
        h = rms_norm(xc, final_norm)
        logits = softcap((h @ w).astype(jnp.float32), final_softcap or None)
        logz = jax.nn.logsumexp(logits, axis=-1)
        vio = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        gold = jnp.sum(jnp.where(vio == lc[..., None], logits, 0.0), -1)
        return acc + jnp.sum((logz - gold) * mc), None

    total, _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                            jnp.zeros((), jnp.float32), (xs, ls, ms))
    return total / jnp.maximum(mask.sum(), 1)


def cross_entropy_loss(logits, labels, mask=None):
    """Token-level CE; logits (B,S,V) possibly vocab-sharded under pjit.

    gold logit extraction uses an iota-compare masked sum instead of
    take_along_axis: a dynamic gather over a sharded vocab axis forces
    GSPMD to all-gather the logits (GBs); the masked sum stays local and
    reduces to a per-token scalar all-reduce."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
