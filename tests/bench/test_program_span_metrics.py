"""The per-layer metrics that read the program's own records: each share
of the window its phases take, and the padding efficiency of the
launches, on synthetic records, and None where there is nothing to read."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness  # noqa: E402

SHARES = {"fleet_transfer_share": ("d2h", "fetch", "h2d"),
          "fleet_stage_share": ("stage",),
          "fleet_host_share": ("plan", "tasks", "scatter"),
          "fleet_verify_share": ("verify",),
          "fleet_kernel_wait_share": ("kernel",)}
KINDS = ("train", "decode")
PHASES = {"d2h": 0.5, "plan": 0.25, "tasks": 0.125, "stage": 1.0,
          "kernel": 0.75, "fetch": 0.0625, "scatter": 0.375,
          "verify": 0.1875, "h2d": 0.03125}


def _record(phases=None, padded_flops=None, m=16, n=64, q=32):
    """A record as the program makes it; ``None`` leaves the field out,
    as on a program without the spans."""
    r = SimpleNamespace(m=m, n=n, q=q, flops=2.0 * m * n * q)
    if phases is not None:
        r.phases = phases
    if padded_flops is not None:
        r.padded_flops = padded_flops
    return r


def _ctx(kind, records, window_s=10.0):
    return {"kind": kind, "records": records, "window_s": window_s}


def _read(name, kind, ctx):
    return harness.load_module("metrics", f"{name}.{kind}").read(ctx)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_sums_its_phases_over_the_window(name, kind):
    records = [_record(dict(PHASES)), _record(dict(PHASES))]
    want = 100.0 * 2 * sum(PHASES[p] for p in SHARES[name]) / 10.0
    assert _read(name, kind, _ctx(kind, records)) == pytest.approx(want)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_reads_nothing_without_its_phases(name, kind):
    other = "decode" if kind == "train" else "train"
    assert _read(name, kind, _ctx(other, [_record(dict(PHASES))])) is None
    assert _read(name, kind, _ctx(kind, [])) is None
    # a program whose records have no phases (the parent of the spans)
    assert _read(name, kind, _ctx(kind, [_record(), _record()])) is None
    # records with phases, none of them this metric's
    assert _read(name, kind, _ctx(kind, [_record({"elsewhere": 1.0})])) \
        is None


@pytest.mark.parametrize("kind", KINDS)
def test_pad_efficiency(kind):
    # 2mnq = 65536 per record; launches ran 4x and 2x that
    records = [_record(padded_flops=4 * 65536.0),
               _record(padded_flops=2 * 65536.0)]
    got = _read("fleet_pad_efficiency", kind, _ctx(kind, records))
    assert got == pytest.approx(100.0 * 2 / 6)
    assert 0 < got <= 100


@pytest.mark.parametrize("kind", KINDS)
def test_pad_efficiency_reads_nothing_without_launches(kind):
    other = "decode" if kind == "train" else "train"
    read = harness.load_module("metrics", f"fleet_pad_efficiency.{kind}").read
    assert read(_ctx(other, [_record(padded_flops=1e6)])) is None
    assert read(_ctx(kind, [])) is None
    assert read(_ctx(kind, [_record(), _record()])) is None
    # the numpy executor launches no buckets: padded_flops stays 0
    assert read(_ctx(kind, [_record(padded_flops=0.0)])) is None


def test_the_benchmark_lists_each_reader_in_its_cell():
    bench = harness.benchmark()
    cells = {"train": "opt-1.3b-l4.train-4x512",
             "decode": "opt-13b-l2.decode-chat-16"}
    moves = {"train": "train_tokens_per_s", "decode": "decode_tokens_per_s"}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in list(SHARES) + ["fleet_pad_efficiency"]:
        for kind in KINDS:
            m = entries[f"{name}.{kind}"]
            assert m["source"] == "program_span" and m["unit"] == "%"
            assert m["workloads"] == [cells[kind]]
            assert m["moves"] == moves[kind]
