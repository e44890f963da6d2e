"""Operation and byte counts kept with the benchmark, and the table of
peaks they are held against.

Training FLOPs per token follow the PaLM paper (arXiv:2204.02311,
appendix B): 6 N + 12 L H Q T, where N counts the matmul parameters (the
output head included, embedding lookups not), L layers, H heads of size Q
and T the sequence length.  Decode counts 2 N per generated token plus
4 L d ctx for attention over the token's own context.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """``{"flops": FLOP/s, "bytes": B/s}`` of one chip; a device missing
    from the table is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS_FILE}")
    row = table[device_kind]
    return {"flops": float(row["bf16_flops"]), "bytes": float(row["hbm_bytes_per_s"])}


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matmul per token: every projection
    and the output head (over the published vocabulary)."""
    L, d = int(config["n_layers"]), int(config["d_model"])
    H, K = int(config["n_heads"]), int(config["n_kv_heads"])
    hd, ff = int(config["head_dim"]), int(config["d_ff"])
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = 3 * d * ff
    return L * (attn + mlp) + d * int(config["vocab_size"])


def train_flops_per_token(config: dict, seq: int) -> float:
    L, H, Q = (int(config["n_layers"]), int(config["n_heads"]),
               int(config["head_dim"]))
    return 6.0 * matmul_params(config) + 12.0 * L * H * Q * seq


def decode_flops(config: dict, contexts: Iterable[int]) -> float:
    """FLOPs of generating one token at each context length given."""
    n2 = 2.0 * matmul_params(config)
    a = 4.0 * int(config["n_layers"]) * int(config["d_model"])
    return sum(n2 + a * c for c in contexts)


def gemm_least_time(m: int, n: int, q: int, itemsize: int,
                    peak: dict) -> float:
    """Least time of one (m, n) x (n, q) GEMM on a chip: the larger of its
    FLOPs over the peak rate and its operand and result bytes over the
    memory bandwidth."""
    return max(2.0 * m * n * q / peak["flops"],
               (m * n + n * q + m * q) * itemsize / peak["bytes"])
