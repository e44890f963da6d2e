"""The check that decides ``correct``, driven through a whole run of each
loop at a size the CPU holds (the chip's look skipped), with the timed
path sound and then broken underneath: the cells' own limits must pass the
sound run and fail every fault, and the control must fail them too."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.chip import compare, harness, weights  # noqa: E402
from benchmarks.chip import traffic as gen  # noqa: E402

TINY = {"name": "tiny", "source": "test", "reference": "dense_decoder",
        "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab_size": 250,
        "norm_eps": 1e-5, "rope_theta": 1e4, "tie_embeddings": True,
        "dtype": "float32", "param_dtype": "float32",
        "optimizer": {"lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1, "grad_clip": 1.0,
                      "warmup_steps": 0, "total_steps": 10000,
                      "min_lr_ratio": 0.1}}
TRAIN = {"name": "t", "kind": "train", "batch": 4, "seq": 64,
         "fleet": {"devices": 16, "seed": 0}, "checked_steps": 3}
DECODE = {"name": "d", "kind": "closed_decode", "slots": 4, "clients": 4,
          "page_size": 16, "max_len": 96, "fleet": {"devices": 16, "seed": 0},
          "pool": 32, "lengths_seed": 0,
          "prompt": {"dist": "uniform", "min": 16, "max": 48, "round": 16},
          "output": {"dist": "uniform", "min": 4, "max": 12, "round": 1}}
SEED = 2 ** 33 + 21


@pytest.fixture(autouse=True)
def numpy_fleet(monkeypatch):
    """The loops run the fleet on the jax executor, as on the chip; here the
    numpy executor stands in for it, so that the CPU runs a cell quickly."""
    from repro.api import CleaveRuntime
    for name in ("train_session", "serve_session"):
        def session(self, *a, _real=getattr(CleaveRuntime, name), **kw):
            return _real(self, *a, **dict(kw, backend="numpy"))
        monkeypatch.setattr(CleaveRuntime, name, session)


def _cell(traffic, limits_of):
    limits = harness.load_json(os.path.join(
        harness.HERE, "cells", limits_of + ".json"))["limits"]
    return {"name": "tiny", "workload": {"chips": 1}, "config": TINY,
            "traffic": traffic, "limits": limits, "per_layer": [],
            "end_to_end": [], "loop": harness.load_module("loops",
                                                          traffic["kind"])}


def _correct(cell, seconds):
    out = cell["loop"].run(cell, SEED, seconds, False,
                           harness.CompileClock(), time.perf_counter())
    checks = harness.judge(out["readings"], cell["limits"])
    return all(c["ok"] for c in checks.values()) and out["failed"] == 0, \
        out["readings"]


@pytest.fixture
def train_cell():
    return _cell(TRAIN, "opt-1.3b-l4.train-4x512")


@pytest.fixture
def decode_cell():
    return _cell(DECODE, "opt-13b-l2.decode-chat-16")


def _break_train_step(monkeypatch, fault, from_step=0):
    """Break every step from ``from_step`` on (the window's steps follow
    the ``checked_steps`` of set-up)."""
    from repro.train_loop.train_step import FleetTrainSession
    real = FleetTrainSession.step
    calls = []

    def step(self, params, opt_state, batch, **kw):
        calls.append(1)
        if len(calls) <= from_step:
            return real(self, params, opt_state, batch, **kw)
        if fault == "half_batch":
            half = batch["tokens"].shape[0] // 2
            return real(self, params, opt_state,
                        {k: v[:half] for k, v in batch.items()}, **kw)
        _, _, metrics = real(self, params, opt_state, batch, **kw)
        return params, opt_state, metrics           # state_unchanged

    monkeypatch.setattr(FleetTrainSession, "step", step)


@pytest.mark.parametrize("fault,from_step", [
    (None, 0), ("state_unchanged", 0), ("half_batch", 0),
    # a fault that starts only once the window opens, after set-up's steps
    ("state_unchanged", 3), ("half_batch", 3)])
def test_train_check(train_cell, monkeypatch, fault, from_step):
    if fault:
        _break_train_step(monkeypatch, fault, from_step)
    ok, readings = _correct(train_cell, 0.0)
    assert ok == (fault is None), readings


@pytest.mark.parametrize("fault", [None, "token_altered", "cache_unchanged"])
def test_decode_check(decode_cell, monkeypatch, fault):
    if fault == "cache_unchanged":
        # a decode step that leaves the KV cache as it found it
        from repro.serving.kv_cache import PagedKVCache
        monkeypatch.setattr(PagedKVCache, "write_tokens",
                            lambda self, *a, **kw: None)
    elif fault:
        from repro.serving.decode_session import ServeSession
        real = ServeSession.step

        def step(self, *a, **kw):
            rep = real(self, *a, **kw)
            if rep is not None and self.step_index == 6:
                req = self.batcher.finished[-1] if not self.batcher.active \
                    else self.batcher.active[0]
                req.tokens[-1] = (req.tokens[-1] + 1) % TINY["vocab_size"]
            return rep

        monkeypatch.setattr(ServeSession, "step", step)
    ok, readings = _correct(decode_cell, 1.0)
    assert ok == (fault is None), readings


def test_decode_run_reports_the_cells_metrics(decode_cell):
    """A run's end-to-end metrics are those the cell names in
    ``BENCHMARK.json``, each above 0."""
    out = decode_cell["loop"].run(decode_cell, SEED, 1.0, False,
                                  harness.CompileClock(), time.perf_counter())
    cell = harness.cell("opt-13b-l2.decode-chat-16")
    assert set(out["end_to_end"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v > 0 for v in out["end_to_end"].values())


def test_train_control_fails_the_limits(train_cell):
    """The reference with float8 GEMMs, put in the program's place."""
    ref = harness.load_module("references", "dense_decoder")
    batches = [gen.train_batch(TRAIN, TINY["vocab_size"], SEED, j)
               for j in range(5)]
    p = weights.make(TINY, SEED)
    readings = compare.train_readings(
        ref.train(TINY, p, batches, precision="fp8"),
        ref.train(TINY, p, batches))
    checks = harness.judge(readings, train_cell["limits"])
    assert not all(c["ok"] for k, c in checks.items()
                   if k in readings), readings


def test_decode_control_fails_the_limits(decode_cell):
    # an untied head of std 1/sqrt(d) gives logits of about unit scale, as a
    # head tied to an embedding of std 0.02 does at the cells' widths (d 2048
    # and 5120) but not at d 64
    tiny = dict(TINY, tie_embeddings=False)
    ref = harness.load_module("references", "dense_decoder")
    p = weights.make(tiny, SEED)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, 40, dtype=np.int32) for _ in range(4)]
    # greedy continuations of the reference itself, 32 tokens each
    served = [[] for _ in prompts]
    for _ in range(32):
        lg = ref.served_logits(tiny, p, prompts,
                               [s + [0] for s in served], 96)
        for s, row in zip(served, lg):
            s.append(int(np.argmax(row[-1])))
    low = ref.served_logits(tiny, p, prompts, served, 96, precision="fp8")
    want = ref.served_logits(tiny, p, prompts, served, 96)
    assert compare.decode_readings(served, want)["token_gap"] == 0.0
    readings = compare.decode_readings([np.argmax(x, -1) for x in low], want)
    checks = harness.judge(readings, decode_cell["limits"])
    assert not checks["token_gap"]["ok"], readings
