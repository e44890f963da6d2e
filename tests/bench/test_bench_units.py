"""The chip benchmark's arithmetic, on the CPU: trace reduction, FLOP and
byte counts, traffic generation and the window's rate and tail."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.chip import flops, harness, trace  # noqa: E402
from benchmarks.chip import traffic as gen  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ------------------------------------------------------------------ trace --

def _hand_trace():
    return {
        "device_ops": {"/device:TPU:0": [["fusion", 0, 10], ["dot", 5, 20],
                                         ["copy", 40, 50], ["late", 90, 120]],
                       "/device:TPU:1": []},
        "spans": [["bench.window", 0, 100], ["bench.execute_step", 0, 30],
                  ["bench.execute_step", 35, 60], ["bench.kv_gather", 60, 80]],
    }


def test_trace_busy_idle_and_spans():
    r = trace.reduce(_hand_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [0,20] + [40,50] + [90,100] (the last op clipped to the window)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["busy_in_span_s"]["bench.execute_step"] == pytest.approx(30e-9)
    assert r["busy_in_span_s"]["bench.kv_gather"] == 0.0
    assert r["span_s"]["bench.execute_step"] == pytest.approx(55e-9)


def test_trace_breakdown():
    t = _hand_trace()
    ops = dict(trace.top_ops(t))
    assert ops == pytest.approx({"dot": 15e-9, "fusion": 10e-9,
                                 "copy": 10e-9, "late": 10e-9})
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["bench.kv_gather", pytest.approx(40e-9)]
    assert gaps[1] == ["bench.execute_step", pytest.approx(20e-9)]


def test_trace_needs_one_window():
    t = _hand_trace()
    t["spans"] = t["spans"][1:]
    with pytest.raises(RuntimeError):
        trace.reduce(t)


def test_recorded_trace():
    """A trace recorded on a TPU v5 lite by ``record_trace.py``: two host
    spans of three jitted matmul programs each, 20 ms host sleeps around
    them."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        t = json.load(f)
    r = trace.reduce(t)
    (plane, ops), = t["device_ops"].items()
    assert plane == "/device:TPU:0" and len(ops) == 24
    assert r["window_s"] == pytest.approx(0.084531569)
    # six 2048^3 bf16 matmul pairs, ~0.18 ms each, plus their copies
    assert r["busy_s"] == pytest.approx(0.001142411)
    assert r["span_s"]["bench.execute_step"] == pytest.approx(0.00249421)
    # the longest idle stretch is a host sleep outside the spans
    name, longest = trace.idle_gaps(t)[0]
    assert name == "host" and longest > 0.02
    # the device plane's clock reads about 1 ms early against the host's:
    # each burst ends before the span that launched it starts, so busy
    # time inside spans misses up to ~1 ms at each span's start
    spans = trace.spans_named(t, "bench.execute_step")
    bursts = [ops[:12], ops[12:]]
    for (a, _), burst in zip(spans, bursts):
        assert 0.5e6 < a - burst[0][1] < 1.5e6
    assert r["busy_in_span_s"]["bench.execute_step"] == 0.0


# ------------------------------------------------------------------ flops --

CONFIGS = {n: harness.load_json(harness.find("configs", n))
           for n in ("opt-1.3b-l4", "opt-13b-l2")}
# the OPT configurations' counts live in their reference module; ``flops``
# calls them by the configuration's ``reference``
DENSE = harness.load_module("references", "dense_decoder")


@pytest.mark.parametrize("name,n_matmul", [
    # 4 x (4 x 2048^2 + 3 x 2048 x 5504) + 2048 x 50272
    ("opt-1.3b-l4", 4 * (4 * 2048 ** 2 + 3 * 2048 * 5504) + 2048 * 50272),
    # 2 x (4 x 5120^2 + 3 x 5120 x 13696) + 5120 x 50272
    ("opt-13b-l2", 2 * (4 * 5120 ** 2 + 3 * 5120 * 13696) + 5120 * 50272),
])
def test_matmul_params_hand_count(name, n_matmul):
    assert DENSE.matmul_params(CONFIGS[name]) == n_matmul


def test_train_flops_per_token_hand_count():
    c = CONFIGS["opt-1.3b-l4"]
    n = DENSE.matmul_params(c)
    assert n == 305_332_224
    # 6 N + 12 L H Q T with L 4, H 32, Q 64, T 512
    assert flops.train_flops_per_token(c, 512) == 6 * n + 12 * 4 * 32 * 64 * 512
    assert flops.train_flops_per_token(c, 512) == pytest.approx(1.8823e9,
                                                                rel=1e-4)


def test_decode_flops_hand_count():
    c = CONFIGS["opt-13b-l2"]
    n = DENSE.matmul_params(c)
    got = flops.decode_flops(c, [300, 1000])
    assert got == 2 * (2 * n) + 4 * 2 * 5120 * (300 + 1000)


def test_gemm_least_time_picks_the_binding_bound():
    peak = {"flops": 197e12, "bytes": 819e9}
    # a (16, 5120) x (5120, 13696) decode GEMM in bf16 is bound by bytes
    t = flops.gemm_least_time(16, 5120, 13696, 2, peak)
    assert t == pytest.approx((16 * 5120 + 5120 * 13696 + 16 * 13696) * 2
                              / 819e9)
    # a square 8192 GEMM is bound by FLOPs
    t = flops.gemm_least_time(8192, 8192, 8192, 2, peak)
    assert t == pytest.approx(2 * 8192 ** 3 / 197e12)


def test_peaks_table_is_keyed_by_device_kind():
    assert flops.peaks("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# ---------------------------------------------------------------- traffic --

TRAFFIC = {n: harness.load_json(harness.find("traffic", n))
           for n in ("train-4x512", "decode-chat-16")}


def test_train_batches_follow_the_seed():
    tr = TRAFFIC["train-4x512"]
    a = gen.train_batch(tr, 50272, 2 ** 33 + 5, 0)
    b = gen.train_batch(tr, 50272, 2 ** 33 + 5, 0)
    c = gen.train_batch(tr, 50272, 2 ** 33 + 5, 1)
    d = gen.train_batch(tr, 50272, 5, 0)
    assert a["tokens"].shape == (4, 512)
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])


def test_requests_follow_the_seed_over_one_multiset():
    tr = TRAFFIC["decode-chat-16"]

    def take(seed, n):
        it = gen.requests(tr, 50272, seed)
        return [next(it) for _ in range(n)]

    n = int(tr["pool"])
    a, b, c = take(2 ** 32 + 9, n), take(2 ** 32 + 9, n), take(7, n)
    for (pa, oa), (pb, ob) in zip(a, b):
        np.testing.assert_array_equal(pa, pb)
        assert oa == ob
    sizes = sorted((len(p), o) for p, o in a)
    assert sizes == sorted((len(p), o) for p, o in c)
    assert [(len(p), o) for p, o in a] != [(len(p), o) for p, o in c]
    grid = set(gen.length_grid(tr["prompt"]))
    assert {len(p) for p, _ in a} <= grid
    assert all(len(p) + o <= int(tr["max_len"]) for p, o in a)
    assert [len(p) for p in gen.warmup_prompts(tr, 50272, 1)] \
        == sorted(grid)


def test_chat_lengths_are_lognormal_around_the_traces_medians():
    # the Azure 2023 conversation trace: median prompt 1020, reply 129
    sizes = gen.request_sizes(TRAFFIC["decode-chat-16"])
    p = np.array([s[0] for s in sizes])
    o = np.array([s[1] for s in sizes])
    assert p.min() >= 128 and p.max() <= 1536 and np.all(p % 128 == 0)
    assert 16 <= o.min() and o.max() <= 512
    assert np.median(p) in (1024, 1152)      # 1020, rounded up to 128
    assert 110 <= np.median(o) <= 150
    assert p.max() + o.max() <= TRAFFIC["decode-chat-16"]["max_len"]


# ----------------------------------------------------------------- window --

def test_window_rate_and_tail_with_a_stall():
    stats = harness.load_module("loops", "closed_decode").window_stats
    # two requests decoding together at 1 s a step, one 5 s stall at 4 -> 9;
    # request 1 joins late (its first token, at 9, is not a gap)
    times = [[0.0, 1.0, 2.0, 3.0, 4.0, 9.0, 10.0],
             [9.0, 10.0]]
    s = stats([100, 50], times, t0=0.5, t1=10.0)
    # tokens after t0: 6 of request 0 and 2 of request 1, over 9.5 s
    assert s["tokens_per_s"] == pytest.approx(8 / 9.5)
    assert s["contexts"] == [101, 102, 103, 104, 105, 106, 50, 51]
    # gaps ending after t0: 1, 1, 1, 1, 5, 1 and 1
    assert s["gaps"] == 7
    assert s["itl_p95_ms"] == pytest.approx(
        1e3 * np.percentile([1, 1, 1, 1, 5, 1, 1], 95))
    assert s["itl_p95_ms"] > 3000
