"""The one traffic generator: every mix is a data file under ``traffic/``
whose parameters this module reads.

Everything is drawn from ``--seed`` except the multiset of request sizes,
which comes from the mix's own ``lengths_seed``: every seed serves the same
sizes, in another order, so that two seeds do the same work.
"""
from __future__ import annotations

import math
from typing import Iterator, List, Tuple

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def length_grid(spec: dict) -> List[int]:
    """Every length a size spec can draw, in increasing order."""
    r = int(spec.get("round", 1))
    lo = r * math.ceil(int(spec["min"]) / r)
    return list(range(lo, int(spec["max"]) + 1, r))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a size spec: ``lognormal`` (median, sigma) or
    ``uniform`` over the grid, clipped to [min, max] and rounded up to a
    multiple of ``round``."""
    r = int(spec.get("round", 1))
    if spec["dist"] == "lognormal":
        x = float(spec["median"]) * np.exp(float(spec["sigma"])
                                           * rng.standard_normal(n))
        x = np.clip(x, int(spec["min"]), int(spec["max"]))
        out = r * np.ceil(x / r)
        return np.minimum(out, int(spec["max"])).astype(np.int64)
    if spec["dist"] == "uniform":
        grid = np.asarray(length_grid(spec), np.int64)
        return grid[rng.integers(0, len(grid), n)]
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    """Step ``step``'s batch: fresh uniform token rows, next-token labels."""
    b, s = int(traffic["batch"]), int(traffic["seq"])
    toks = _rng(seed, 1, step).integers(0, vocab, (b, s + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def request_sizes(traffic: dict) -> List[Tuple[int, int]]:
    """The mix's fixed multiset of (prompt length, reply length)."""
    rng = np.random.default_rng(int(traffic["lengths_seed"]))
    n = int(traffic["pool"])
    prompts = draw_lengths(traffic["prompt"], n, rng)
    outs = draw_lengths(traffic["output"], n, rng)
    return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def requests(traffic: dict, vocab: int, seed: int
             ) -> Iterator[Tuple[np.ndarray, int]]:
    """Endless stream of (prompt token ids, reply length): the fixed
    multiset in a seeded order, reshuffled each time it runs out."""
    sizes = request_sizes(traffic)
    order_rng, tok_rng = _rng(seed, 2), _rng(seed, 3)
    while True:
        for i in order_rng.permutation(len(sizes)):
            p, o = sizes[i]
            yield tok_rng.integers(0, vocab, p, dtype=np.int32), o


def warmup_prompts(traffic: dict, vocab: int, seed: int) -> List[np.ndarray]:
    """One prompt of every length the mix can draw (set-up compiles each
    prefill shape once, through the session's own admission path)."""
    rng = _rng(seed, 4)
    return [rng.integers(0, vocab, p, dtype=np.int32)
            for p in length_grid(traffic["prompt"])]
