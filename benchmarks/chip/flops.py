"""Operation and byte counts kept with the benchmark, and the table of
peaks they are held against.

A model's FLOPs depend on its architecture, so they are counted by the
configuration's reference module (``references/<reference>.py``), which
defines ``train_flops_per_token`` and ``decode_flops``; the functions of
the same names here call that module's.  The dense decoder's
follow the PaLM paper (arXiv:2204.02311, appendix B): 6 N + 12 L H Q T for
training, and 2 N + 4 L d ctx per generated token.  The byte and FLOP
bound of one GEMM, ``gemm_least_time``, holds for any architecture.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

from benchmarks.chip import harness

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


def train_flops_per_token(config: dict, seq: int) -> float:
    """Training FLOPs per token at sequence length ``seq``, as the
    configuration's reference module counts them."""
    return harness.reference(config).train_flops_per_token(config, seq)


def decode_flops(config: dict, contexts: Iterable[int]) -> float:
    """FLOPs of generating one token at each context length given, as the
    configuration's reference module counts them."""
    return harness.reference(config).decode_flops(config, contexts)


def gemm_least_time(m: int, n: int, q: int, itemsize: int,
                    peak: dict) -> float:
    """Least time of one (m, n) x (n, q) GEMM on a chip: the larger of its
    FLOPs over the peak rate and its operand and result bytes over the
    memory bandwidth."""
    return max(2.0 * m * n * q / peak["flops"],
               (m * n + n * q + m * q) * itemsize / peak["bytes"])
