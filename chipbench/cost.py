"""Operations and bytes that the algorithms need, from the
configuration's shapes alone: never from the arrays a program holds,
so a share of a peak computed from them cannot pass 100% whatever the
program stores or skips."""
from __future__ import annotations

from typing import Sequence, Tuple


def peak(peaks: dict, devices) -> dict:
    """The peaks of the device the run used; an unknown device is an
    error."""
    kind = devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def decode_step(config: dict, positions: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step for live slots writing at
    ``positions``: every weight once at the compute precision (bf16),
    the new token's embedding row, and the KV rows up to and including
    each slot's position (bf16); FLOPs of the live tokens' products and
    attention."""
    D, F, V = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    L, H, KV, Dh = (config["num_hidden_layers"],
                    config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    matmul = L * (2 * D * H * Dh + 2 * D * KV * Dh + 3 * D * F) + D * V
    rows = sum(p + 1 for p in positions)
    flops = 2 * matmul * len(positions) + L * 4 * H * Dh * rows
    nbytes = 2 * matmul + 2 * D * len(positions) + L * 2 * KV * Dh * 2 * rows
    return float(flops), float(nbytes)


def jacobi_sweep(config: dict, chips: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one sweep on one chip: 4 operations per
    interior element, each read once and written once (4 + 4 bytes)."""
    interior = (config["grid_rows"] - 2) * (config["grid_cols"] - 2)
    return 4.0 * interior / chips, 8.0 * interior / chips
