"""The program's own spans (``core.spans``): every fleet GEMM's round trip
is split into nine phases that cover it, the step loops record their
phases on their reports, and a profiler capture shows the spans nested as
the code nests them."""
import glob

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.api import CleaveRuntime, Fleet  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core import cost_model as cm  # noqa: E402
from repro.core.spans import span  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim import adam  # noqa: E402

FLEET_PHASES = {"d2h", "plan", "tasks", "stage", "kernel", "fetch",
                "scatter", "verify", "h2d"}
CHUNKS = dict(q_chunk=16, k_chunk=16, loss_chunk=16)


def _batch(cfg, step, b=2, s=32):
    t = np.random.default_rng(step).integers(0, cfg.vocab_size, (b, s + 1))
    return {"tokens": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}


@pytest.fixture(scope="module")
def train():
    """A jax-backend fleet training session at level dispatch, past its
    first (cold) step."""
    cfg = get_config("llama3-8b").reduced()
    opt_cfg = adam.AdamConfig()
    state = {"params": M.init_params(cfg, jax.random.PRNGKey(0))}
    state["opt"] = adam.init(state["params"], opt_cfg)
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
    sess = rt.train_session(opt_cfg, backend="jax", kernel="xla", **CHUNKS)
    assert sess.dispatch == "level"

    def step():
        i = sess.step_index
        state["params"], state["opt"], m = sess.step(
            state["params"], state["opt"], _batch(cfg, i))
        return m["fleet"]

    step()
    return step


def _check_round_trips(records):
    assert records
    for r in records:
        assert set(r.phases) == FLEET_PHASES
        assert all(v >= 0 for v in r.phases.values())
        assert sum(r.phases.values()) <= r.roundtrip_time
        assert r.padded_flops >= r.flops > 0
        # the Freivalds check runs inside the round trip at level dispatch
        assert r.verify_time == r.phases["verify"] > 0
    covered = sum(sum(r.phases.values()) for r in records)
    assert covered >= 0.9 * sum(r.roundtrip_time for r in records)


def test_warm_train_step_phases_cover_the_round_trips(train):
    rep = train()
    assert rep.plan_cache_hit_rate == 1.0
    _check_round_trips(rep.records)
    assert rep.fleet_verify_time > 0
    assert rep.fleet_verify_time == pytest.approx(
        sum(r.verify_time for r in rep.records))
    ph = rep.phases
    assert {"step", "forward_backward", "adam"} <= set(ph)
    assert ph["step"] == rep.wall_time
    assert ph["forward_backward"] + ph["adam"] <= ph["step"]
    # the fleet's round trips run inside forward and backward
    assert ph["gemm"] == pytest.approx(
        sum(r.roundtrip_time for r in rep.records))
    assert ph["gemm"] <= ph["forward_backward"]
    assert ph["kernel"] == pytest.approx(
        sum(r.phases["kernel"] for r in rep.records))
    line = rep.log_line()
    assert " | spans " in line and " step " in line


def test_profiler_capture_nests_the_spans(train, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        rep = train()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("cleave.")]

    def inside(name, parent):
        outer = [(a, b) for n, a, b in events if n == parent]
        inner = [(a, b) for n, a, b in events if n == name]
        assert inner and outer, (name, parent)
        return all(any(a0 <= a and b <= b0 for a0, b0 in outer)
                   for a, b in inner)

    for child in FLEET_PHASES:
        assert inside(f"cleave.fleet.{child}", "cleave.fleet.gemm"), child
    assert sum(n == "cleave.fleet.gemm" for n, _, _ in events) \
        == len(rep.records)
    assert inside("cleave.fleet.gemm", "cleave.train.forward_backward")
    for name in ("cleave.train.forward_backward", "cleave.train.adam"):
        assert inside(name, "cleave.train.step")


def test_warm_decode_step_phases_cover_the_round_trips():
    cfg = get_config("llama3-8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
    sess = rt.serve_session(params, slots=2, page_size=4, max_len=16,
                            backend="jax", kernel="xla")
    for p in ([1, 2, 3, 4], [5, 6, 7]):
        sess.submit(np.asarray(p), 4)
    sess.step()
    rep = sess.step()
    assert rep.n_admitted == 0 and rep.plan_cache_hit_rate == 1.0
    _check_round_trips(rep.records)
    ph = rep.phases
    steps = {"step", "admit", "gather", "kv_upload", "decode", "sample",
             "kv_write"}
    assert steps <= set(ph)
    assert sum(ph[k] for k in steps - {"step"}) <= ph["step"]
    assert ph["gemm"] <= ph["decode"]
    report = sess.run()
    assert report.phases["step"] == pytest.approx(
        sum(s.phases["step"] for s in sess.step_reports))
    line = report.log_line()
    assert " | spans " in line and " step " in line


def test_numpy_executor_reports_the_phases_it_has():
    rt = CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(8, seed=0))
    rng = np.random.default_rng(0)
    A = rng.standard_normal((96, 64))
    B = rng.standard_normal((64, 80))
    s = rt.execute_step(A, B, gemm=cm.GEMM(m=96, n=64, q=80))
    assert set(s.phases) == {"plan", "tasks", "verify"}
    assert s.padded_flops == 0.0
    s = rt.execute_step(A, B, gemm=cm.GEMM(m=96, n=64, q=80),
                        verify=False)
    assert set(s.phases) == {"plan", "tasks"}


def test_span_accumulates_by_short_name():
    phases = {}
    for _ in range(3):
        with span("cleave.test.inner_part", phases):
            pass
    with span("cleave.test.unrecorded"):
        pass
    assert list(phases) == ["inner_part"] and phases["inner_part"] >= 0
    with pytest.raises(ValueError):
        with span("cleave.test.raises", phases):
            raise ValueError
    assert "raises" in phases
