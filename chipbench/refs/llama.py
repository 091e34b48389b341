"""Plain reference of a LLaMA-architecture decoder (deepseek-llm-7b).

Written from the published description (arXiv:2401.02954, section 2:
"the micro design largely follows LLaMA"): a token embedding; in every
layer a pre-norm multi-head attention with rotary positions
(rotate-half form) and a pre-norm SwiGLU MLP, each added to the
residual stream; a final RMSNorm and an untied output head.

Float32 throughout, every product at HIGHEST precision, one sequence
at a time, no cache, no kernels.  ``low`` is the control: each operand
of every product is rounded to a lower precision first (bfloat16, or
fp8 e4m3 with a per-tensor scale as an fp8 serving path would use),
the product itself staying exact.

Weights come as a dict of arrays with a leading layer axis:
``embed`` (V, D), ``head`` (D, V), ``final_norm`` (D,), and per layer
``q``/``k``/``v`` (L, D, H*Dh), ``o`` (L, H*Dh, D), ``gate``/``up``
(L, D, F), ``down`` (L, F, D), ``attn_norm``/``mlp_norm`` (L, D).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def quantise(x, low):
    """x rounded to the control's precision and back to float32."""
    if low is None:
        return x
    if low == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if low == "float8_e4m3fn":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown control precision {low!r}")


def _mm(eq, a, b, low):
    return jnp.einsum(eq, quantise(a, low), quantise(b, low),
                      precision=HIGHEST, preferred_element_type=F32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary(x, theta):
    """x (T, H, Dh): rotate-half rotary embedding at positions 0..T-1."""
    T, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2.0 / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(w, tokens, cfg: dict, low=None):
    """tokens (T,) int32 -> next-token logits (T, V) float32."""
    T = tokens.shape[0]
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = w["embed"][tokens].astype(F32)
    for i in range(w["q"].shape[0]):
        h = rms_norm(x, w["attn_norm"][i], eps)
        q = rotary(_mm("td,de->te", h, w["q"][i], low).reshape(T, H, Dh),
                   theta)
        k = rotary(_mm("td,de->te", h, w["k"][i], low).reshape(T, H, Dh),
                   theta)
        v = _mm("td,de->te", h, w["v"][i], low).reshape(T, H, Dh)
        s = _mm("qhd,khd->hqk", q, k, low) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = _mm("hqk,khd->qhd", p, v, low).reshape(T, H * Dh)
        x = x + _mm("te,ed->td", o, w["o"][i], low)
        h = rms_norm(x, w["mlp_norm"][i], eps)
        g = _mm("td,df->tf", h, w["gate"][i], low)
        u = _mm("td,df->tf", h, w["up"][i], low)
        x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, w["down"][i], low)
    return _mm("td,dv->tv", rms_norm(x, w["final_norm"], eps), w["head"],
               low)


def served_gaps(w, seq, cfg: dict):
    """seq (T,): a prompt followed by the tokens served after it.
    Returns (T,): at position t, how far the reference's logit of
    seq[t+1] lies below its best logit there (the last entry is 0)."""
    ref = logits(w, seq, cfg)
    nxt = jnp.concatenate([seq[1:], seq[-1:]])
    got = jnp.take_along_axis(ref, nxt[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


def control_gaps(w, seq, cfg: dict, low: str):
    """As :func:`served_gaps`, for the token that the reference computed
    at ``low`` precision puts first at each position."""
    ref = logits(w, seq, cfg)
    pick = jnp.argmax(logits(w, seq, cfg, low), axis=1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got
