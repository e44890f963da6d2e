"""Device-resident operand staging: the jax backend takes ``jax.Array``
operands as they are, pads them on the device and copies none of them to
the host, save the slices of blocks that the device residuals flag.  Its
outputs are bitwise those of the same values given as host arrays, which
take the float32 host pad and the session ``PadCache``."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.api import CleaveRuntime, Fleet  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core import cost_model as cm  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim import adam  # noqa: E402

FLEET_PHASES = {"d2h", "plan", "tasks", "stage", "kernel", "fetch",
                "scatter", "verify", "h2d"}
# both axes of both operands need padding to the 128 block
G = cm.GEMM(m=200, n=150, q=130)


def _runtime():
    return CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(8, seed=0))


def _operands(dtype, g=G, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((g.m, g.n)).astype(dtype)
    B = rng.standard_normal((g.n, g.q)).astype(dtype)
    return A, B


def _run(A, B, dispatch="level", g=G, **kw):
    """One jax-backend GEMM on a fresh runtime (so both sides draw the
    same Freivalds seeds), inline or split-phase with ``finalize`` run at
    once."""
    rt = _runtime()
    if dispatch == "level":
        return rt.execute_step(A, B, gemm=g, backend="jax", **kw)
    step, fin = rt.execute_step_deferred(A, B, gemm=g, backend="jax", **kw)
    fin()
    return step


def _assert_same(host, dev):
    assert np.array_equal(host.output, dev.output)
    assert host.verified == dev.verified
    assert host.n_recovered == dev.n_recovered


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("dtype,policy", [
    (np.float32, "f32"), (np.float32, "bf16"),
    (jnp.bfloat16, "f32"), (jnp.bfloat16, "bf16")])
def test_device_operands_match_host_operands_bitwise(dtype, policy, kernel):
    A, B = _operands(dtype)
    host = _run(A, B, kernel=kernel, dtype_policy=policy)
    dev = _run(jnp.asarray(A), jnp.asarray(B), kernel=kernel,
               dtype_policy=policy)
    _assert_same(host, dev)
    assert dev.verified
    assert host.host_operand_bytes == A.nbytes + B.nbytes
    assert dev.host_operand_bytes == 0
    assert {"stage", "kernel", "fetch"} <= set(dev.phases)
    assert "d2h" not in dev.phases          # nothing was fetched


@pytest.mark.parametrize("dtype,policy,narrow", [
    (np.float32, "f32", np.float32), (np.float32, "bf16", jnp.bfloat16),
    (jnp.bfloat16, "f32", jnp.bfloat16)])
def test_device_pad_keeps_the_narrower_dtype(dtype, policy, narrow):
    """A device operand is padded in its own dtype or the narrower compute
    dtype; an aligned one comes back as it is, and neither touches the
    cache."""
    compute = {"f32": "float32", "bf16": "bfloat16"}[policy]
    pc = ops.PadCache()
    x = jnp.asarray(np.ones((100, 150), dtype))
    pad = ops._staged_pad(x, 228, 256, "a", pc, compute)
    assert pad.shape == (228, 256) and pad.dtype == jnp.dtype(narrow)
    assert np.array_equal(np.asarray(pad[:100, :150], np.float32),
                          np.ones((100, 150), np.float32))
    assert not np.asarray(pad[100:]).any() and \
        not np.asarray(pad[:, 150:]).any()
    aligned = jnp.asarray(np.ones((128, 256), dtype))
    same = ops._staged_pad(aligned, 128, 256, "b", pc, compute)
    assert (same is aligned) == (jnp.dtype(dtype) == jnp.dtype(narrow))
    assert pc.hits == pc.misses == 0
    assert ops.stage_plan_operands(x, x.T, [(0, 100, 0, 100)]) \
        == (None, None)


@pytest.mark.parametrize("dispatch", ["level", "deferred"])
def test_flagged_block_refetched_from_device_operands(dispatch):
    """A poisoned block is caught by the device residual, checked by the
    host oracle and re-dispatched from its operand slices, fetched from
    the device: only those slices count as host bytes."""
    A, B = _operands(np.float32)
    plan = _runtime().plan_gemm(G)
    bad = plan.assignments[0].device_id
    rects = [(a.r0, a.r1, a.c0, a.c1) for a in plan.assignments
             if a.device_id == bad and a.r1 > a.r0 and a.c1 > a.c0]
    host = _run(A, B, dispatch, corrupt_ids=[bad], kernel="xla",
                dtype_policy="f32")
    dev = _run(jnp.asarray(A), jnp.asarray(B), dispatch, corrupt_ids=[bad],
               kernel="xla", dtype_policy="f32")
    _assert_same(host, dev)
    assert not dev.verified                 # poisoning caught...
    np.testing.assert_allclose(dev.output, A.astype(np.float64) @ B,
                               rtol=1e-4, atol=1e-4)   # ...and healed
    item = A.itemsize
    assert dev.host_operand_bytes == sum(
        (r1 - r0) * G.n * item + G.n * (c1 - c0) * item
        for r0, r1, c0, c1 in rects)
    assert 0 < dev.host_operand_bytes < A.nbytes + B.nbytes
    if dispatch == "level":
        assert dev.phases["d2h"] > 0


def test_failure_recovery_matches_host_operands():
    A, B = _operands(np.float32)
    plan = _runtime().plan_gemm(G)
    victim = plan.assignments[0].device_id
    host = _run(A, B, fail_ids=[victim], kernel="xla")
    dev = _run(jnp.asarray(A), jnp.asarray(B), fail_ids=[victim],
               kernel="xla")
    _assert_same(host, dev)
    assert dev.n_recovered > 0 and dev.verified
    assert [r for r, _ in host.recovery.patches] \
        == [r for r, _ in dev.recovery.patches]
    assert dev.host_operand_bytes == 0


# ------------------------------------------------- fleet GEMM sessions ----

CHUNKS = dict(q_chunk=16, k_chunk=16, loss_chunk=16)


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3-8b").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("dispatch", ["level", "dataflow"])
def test_jax_train_step_copies_no_operand_to_host(cfg, params, dispatch):
    opt_cfg = adam.AdamConfig()
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
    sess = rt.train_session(opt_cfg, backend="jax", kernel="xla",
                            dispatch=dispatch, **CHUNKS)
    t = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 33))
    batch = {"tokens": jnp.asarray(t[:, :-1]),
             "labels": jnp.asarray(t[:, 1:])}
    _, _, m = sess.step(params, adam.init(params, opt_cfg), batch)
    rep = m["fleet"]
    assert rep.records and rep.verified
    for r in rep.records:
        assert r.host_operand_bytes == 0
        assert FLEET_PHASES - {"verify"} <= set(r.phases)
    if dispatch == "level":
        assert all(set(r.phases) == FLEET_PHASES for r in rep.records)
    assert rep.host_operand_bytes == 0
    assert " | host operands 0.0 MB | spans " in rep.log_line()


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_serve_step_host_operand_bytes(cfg, params, backend):
    """The jax backend copies no operand to the host in a decode step; the
    numpy backend copies both of every GEMM."""
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
    sess = rt.serve_session(params, slots=2, page_size=4, max_len=16,
                            backend=backend, kernel="xla")
    sess.submit(np.asarray([1, 2, 3, 4]), 2)
    rep = sess.step()
    assert rep.records and rep.verified
    for r in rep.records:
        want = 0 if backend == "jax" else (r.m * r.n + r.n * r.q) * r.b
        assert r.host_operand_bytes == want
    if backend == "jax":
        assert all(set(r.phases) == FLEET_PHASES for r in rep.records)
    assert rep.host_operand_bytes == sum(r.host_operand_bytes
                                         for r in rep.records)
    report = sess.run()
    assert report.host_operand_bytes == sum(
        s.host_operand_bytes for s in sess.step_reports)
    assert (report.host_operand_bytes == 0) == (backend == "jax")
    assert " | host operands " in report.log_line()
