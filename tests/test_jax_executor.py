"""Backend-equivalence suite: the JAX/Pallas fleet executor must compute
the same numbers as the numpy executor and a monolithic ``jnp.einsum``
oracle — including under injected failures and caught corruption — to
<=1e-5 relative under the f32 dtype policy (§3.2 exact-semantics claim on
the accelerator substrate).  All jax paths run on CPU via interpret=True
(``kernel="pallas"``) or compiled XLA (``kernel="xla"``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CleaveRuntime, Fleet
from repro.core import cost_model as cm, executor, jax_executor
from repro.kernels import block_gemm as bg
from repro.kernels import ops
from repro.sim.devices import sample_fleet

RTOL = 1e-5


def _ab(rng, g):
    A = rng.standard_normal((g.m, g.n)).astype(np.float32)
    B = rng.standard_normal((g.n, g.q)).astype(np.float32)
    return A, B


def _oracle(A, B):
    """The monolithic ``jnp.einsum`` oracle (f32 — JAX's default compute
    precision); both backends must match it to <=1e-5 relative.  For the
    numpy executor's own 1e-9 check use :func:`_exact`."""
    return np.asarray(jnp.einsum("mk,kq->mq", jnp.asarray(A, jnp.float32),
                                 jnp.asarray(B, jnp.float32)),
                      np.float64)


def _exact(A, B):
    return A.astype(np.float64) @ B.astype(np.float64)


def _assert_close(got, want, rtol=RTOL):
    scale = np.max(np.abs(want))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=rtol, atol=rtol * scale)


# ------------------------------------------------------ kernel primitives --

@pytest.mark.parametrize("G,m,k,n,bm", [(1, 128, 128, 128, 64),
                                        (3, 128, 256, 128, 64),
                                        (2, 64, 128, 192, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gemm_batched_matches_einsum(G, m, k, n, bm, dtype, rng):
    a = jnp.asarray(rng.standard_normal((G, m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((G, k, n)), dtype)
    out = bg.block_gemm_batched(a, b, bm=bm, bn=bm, bk=bm,
                                out_dtype=jnp.float32, interpret=True)
    want = jnp.einsum("gmk,gkn->gmn", a.astype(jnp.float32),
                      b.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_plan_gemm_rect_execution(kernel, rng):
    """Uneven, unaligned rectangles (sliver included) crop back exactly."""
    m, n, q = 200, 300, 170
    A = rng.standard_normal((m, n)).astype(np.float32)
    B = rng.standard_normal((n, q)).astype(np.float32)
    C = _oracle(A, B)
    rects = [(0, 128, 0, 37), (0, 128, 37, 170), (128, 200, 0, 169),
             (128, 200, 169, 170),          # width-1 sliver
             (50, 50, 0, 170)]              # degenerate: empty block
    blocks = ops.plan_gemm(A, B, rects, kernel=kernel,
                           compute_dtype="float32")
    for (r0, r1, c0, c1), blk in zip(rects, blocks):
        assert blk.shape == (r1 - r0, c1 - c0)
        if blk.size:
            _assert_close(blk, C[r0:r1, c0:c1])


def test_plan_gemm_rejects_unknown_kernel():
    with pytest.raises(ValueError, match="kernel"):
        ops.resolve_plan_kernel("triton")


def test_dtype_policy_registry():
    assert jax_executor.get_policy("f32").compute_dtype == "float32"
    assert jax_executor.get_policy("bf16").compute_dtype == "bfloat16"
    pol = jax_executor.POLICIES["f32"]
    assert jax_executor.get_policy(pol) is pol
    assert jax_executor.get_policy(None).name in ("f32", "bf16")
    with pytest.raises(ValueError, match="policy"):
        jax_executor.get_policy("f16")
    # sliver blocks get a looser tolerance than wide blocks, never absurd
    assert pol.freivalds_rtol(1024, 32) > pol.freivalds_rtol(1024, 65536)


# ------------------------------------------------- backend equivalence -----

SHAPES = [
    (128, 128, 128, 8),     # aligned
    (200, 300, 170, 8),     # nothing is a multiple of anything
    (96, 512, 64, 12),      # tall contraction
    (257, 129, 131, 16),    # odd primes, more devices
]


@pytest.mark.parametrize("m,n,q,n_dev", SHAPES)
def test_backend_equivalence_sweep(m, n, q, n_dev, rng):
    g = cm.GEMM(m=m, n=n, q=q)
    devs = sample_fleet(n_dev, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    rep_np = executor.execute_plan(g, plan, A, B, devs, rng=0)
    rep_jx = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                           kernel="xla")
    assert rep_np.verified and rep_jx.verified
    assert rep_np.n_tasks == rep_jx.n_tasks
    _assert_close(rep_np.output, _exact(A, B), rtol=1e-9)
    _assert_close(rep_np.output, want)
    _assert_close(rep_jx.output, want)
    _assert_close(rep_jx.output, rep_np.output)


def test_pallas_interpret_parity_with_xla(rng):
    """kernel='pallas' (interpret=True on CPU) and kernel='xla' run the
    same gather/pad/bucket semantics; both match the oracle."""
    g = cm.GEMM(m=160, n=256, q=144)
    devs = sample_fleet(8, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    rep_p = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                          kernel="pallas")
    rep_x = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                          kernel="xla")
    assert rep_p.kernel == "pallas" and rep_x.kernel == "xla"
    _assert_close(rep_p.output, want)
    _assert_close(rep_x.output, want)
    _assert_close(rep_p.output, rep_x.output)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_backend_equivalence_under_failure(kernel, rng):
    g = cm.GEMM(m=192, n=384, q=192)
    devs = sample_fleet(12, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    victims = sorted({a.device_id for a in plan.assignments})[:2]
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    rep_np = executor.execute_plan(g, plan, A, B, devs, fail_ids=victims,
                                   rng=0)
    rep_jx = jax_executor.execute_plan_jax(g, plan, A, B, devs,
                                           fail_ids=victims, rng=0,
                                           kernel=kernel)
    assert rep_np.n_recovered == rep_jx.n_recovered > 0
    assert [r for r, _ in rep_np.recovery.patches] \
        == [r for r, _ in rep_jx.recovery.patches]
    _assert_close(rep_np.output, _exact(A, B), rtol=1e-9)
    _assert_close(rep_jx.output, want)


def test_backend_equivalence_fail_plus_corrupt(rng):
    """Worst case: one device fails mid-level while another poisons its
    block.  Freivalds catches the corruption, recovery fills the hole, and
    both backends still equal the oracle."""
    g = cm.GEMM(m=256, n=512, q=256)
    devs = sample_fleet(16, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    ids = sorted({a.device_id for a in plan.assignments})
    victim, bad = ids[0], ids[1]
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    rep_np = executor.execute_plan(g, plan, A, B, devs, fail_ids=[victim],
                                   corrupt_ids=[bad], rng=0)
    rep_jx = jax_executor.execute_plan_jax(g, plan, A, B, devs,
                                           fail_ids=[victim],
                                           corrupt_ids=[bad], rng=0,
                                           kernel="xla")
    assert not rep_np.verified and not rep_jx.verified   # poisoning caught
    _assert_close(rep_np.output, _exact(A, B), rtol=1e-9)  # ...and healed
    _assert_close(rep_jx.output, want)


def test_corrupt_device_with_degenerate_rect(rng):
    """A corrupting device that also owns a degenerate (zero-area)
    rectangle must not crash the injection path on either backend; its
    real block is still caught and healed."""
    devs = sample_fleet(6, np.random.default_rng(0))
    g = cm.GEMM(m=128, n=128, q=128)
    base = cm.solve_gemm(g, devs)
    bad = base.assignments[0].device_id
    plan = cm.Plan(
        gemm=g,
        assignments=[cm.Assignment(device_id=bad, r0=0, r1=0, c0=0, c1=0)]
        + list(base.assignments),
        makespan=base.makespan, lower_bound=base.lower_bound)
    A, B = _ab(rng, g)
    for rep in (
            executor.execute_plan(g, plan, A, B, devs, corrupt_ids=[bad],
                                  rng=0),
            jax_executor.execute_plan_jax(g, plan, A, B, devs,
                                          corrupt_ids=[bad], rng=0,
                                          kernel="xla")):
        assert not rep.verified
        _assert_close(rep.output, _exact(A, B))


def test_backend_equivalence_n_split_plan(rng):
    """Tiny device memory forces the contraction-dim split (n_split > 1);
    the executors run the same full-n rectangles regardless."""
    g = cm.GEMM(m=64, n=4096, q=64)
    devs = [dataclasses.replace(d, memory=300e3)
            for d in sample_fleet(4, np.random.default_rng(0))]
    plan = cm.solve_gemm(g, devs)
    assert plan.n_split > 1
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    rep_np = executor.execute_plan(g, plan, A, B, devs, rng=0)
    rep_jx = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                           kernel="xla")
    _assert_close(rep_np.output, _exact(A, B), rtol=1e-9)
    _assert_close(rep_jx.output, want)


def test_bf16_policy_runs_with_matching_tolerance(rng):
    """The MXU-native bf16-compute/f32-accumulate policy stays within bf16
    rounding of the oracle and self-verifies (no false Freivalds trips)."""
    g = cm.GEMM(m=128, n=256, q=128)
    devs = sample_fleet(8, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    A, B = _ab(rng, g)
    rep = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                        kernel="xla", policy="bf16")
    assert rep.verified and rep.policy == "bf16"
    _assert_close(rep.output, _oracle(A, B), rtol=3e-2)


# ------------------------------------------- device-side batched Freivalds -

@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("policy", ["f32", "bf16"])
def test_device_freivalds_flags_match_host_path(kernel, policy, rng):
    """Corrupt blocks are flagged identically to the host-side Freivalds
    oracle at the same dtype-policy tolerance, across both kernels and both
    policies.  Under f32 the O(1) poisoning is caught (verified=False) and
    healed exactly like the numpy executor; under bf16 both paths agree
    that a minimum-magnitude single-entry corruption sits below the bf16
    noise floor (the documented physics) — the point is the *verdicts*
    cannot drift."""
    from repro.core.verify import freivalds as host_freivalds
    g = cm.GEMM(m=192, n=256, q=160)
    devs = sample_fleet(10, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    A, B = _ab(rng, g)
    tol = 3e-2 if policy == "bf16" else RTOL
    clean = jax_executor.execute_plan_jax(g, plan, A, B, devs, rng=0,
                                          kernel=kernel, policy=policy)
    assert clean.verified
    _assert_close(clean.output, _oracle(A, B), rtol=tol)
    a = plan.assignments[1]
    bad = a.device_id
    rep = jax_executor.execute_plan_jax(g, plan, A, B, devs,
                                        corrupt_ids=[bad], rng=0,
                                        kernel=kernel, policy=policy)
    # the host path's verdict on the same poisoned policy-precision block
    pol = jax_executor.get_policy(policy)
    blk = jax_executor._redispatch(A[a.r0:a.r1], B[:, a.c0:a.c1],
                                   pol).copy()
    blk[0, 0] += 1.0 + abs(blk[0, 0])
    host_ok = host_freivalds(
        A[a.r0:a.r1], B[:, a.c0:a.c1], blk, np.random.default_rng(0),
        rtol=pol.freivalds_rtol(g.n, a.alpha * a.beta))
    assert rep.verified == host_ok
    if policy == "f32":
        # caught, healed, and consistent with the f64 numpy executor
        rep_host = executor.execute_plan(g, plan, A, B, devs,
                                         corrupt_ids=[bad], rng=0)
        assert rep.verified is False and rep_host.verified is False
        _assert_close(rep.output, _oracle(A, B), rtol=tol)


def test_device_freivalds_residuals_exposed(rng):
    """plan_gemm_buckets emits per-rect (lhs, rhs, scale) residual triples;
    honest blocks agree to the policy tolerance, a corrupted one does not."""
    m, n, q = 160, 192, 256
    A = rng.standard_normal((m, n)).astype(np.float32)
    B = rng.standard_normal((n, q)).astype(np.float32)
    rects = [(0, 96, 0, 128), (0, 96, 128, 256), (96, 160, 0, 256)]
    corrupt = np.array([0.0, 1.0, 0.0], np.float32)
    runs = ops.plan_gemm_buckets(A, B, rects, kernel="xla",
                                 compute_dtype="float32", verify_seed=7,
                                 corrupt=corrupt)
    pol = jax_executor.POLICIES["f32"]
    got = {}
    for run in runs:
        for g_, i in enumerate(run.idx):
            r0, r1, c0, c1 = rects[i]
            rtol = pol.freivalds_rtol(n, (r1 - r0) * (c1 - c0))
            resid = np.abs(run.lhs[g_] - run.rhs[g_])
            bound = rtol * np.abs(run.rhs[g_]) + rtol * run.scale[g_]
            got[i] = bool(np.all(resid <= bound))
            # the emitted blocks carry the corruption the residual saw
            want = _oracle(A, B)[r0:r1, c0:c1].astype(np.float32)
            if corrupt[i]:
                assert abs(run.block(g_)[0, 0] - want[0, 0]) > 1.0
    assert got == {0: True, 1: False, 2: True}


def test_device_freivalds_seed_threading(rng):
    """Residual draws are keyed by (seed, task id): same seed reproduces,
    different seeds vary, and bucketing does not change a task's draw."""
    m, n, q = 128, 128, 256
    A = rng.standard_normal((m, n)).astype(np.float32)
    B = rng.standard_normal((n, q)).astype(np.float32)
    rects = [(0, 128, 0, 128), (0, 128, 128, 256)]
    r1 = ops.plan_gemm_buckets(A, B, rects, kernel="xla",
                               compute_dtype="float32", verify_seed=3)
    r2 = ops.plan_gemm_buckets(A, B, rects, kernel="xla",
                               compute_dtype="float32", verify_seed=3)
    r3 = ops.plan_gemm_buckets(A, B, rects, kernel="xla",
                               compute_dtype="float32", verify_seed=4)
    np.testing.assert_array_equal(r1[0].lhs, r2[0].lhs)
    assert not np.array_equal(r1[0].lhs, r3[0].lhs)


def test_pad_cache_reuses_device_operands(rng):
    """The runtime step loop's padded-operand staging cache: repeated
    plan_gemm calls with the same operands hit instead of re-staging."""
    m, n, q = 100, 150, 120
    A = rng.standard_normal((m, n)).astype(np.float32)
    B = rng.standard_normal((n, q)).astype(np.float32)
    rects = [(0, 100, 0, 60), (0, 100, 60, 120)]
    pc = ops.PadCache()
    want = _oracle(A, B)
    for _ in range(3):
        blocks = ops.plan_gemm(A, B, rects, kernel="xla",
                               compute_dtype="float32", pad_cache=pc)
        for (r0, r1, c0, c1), blk in zip(rects, blocks):
            _assert_close(blk, want[r0:r1, c0:c1])
    assert pc.misses == 2 and pc.hits == 4        # a_pad + b_pad staged once
    # a different operand array is a miss, not a stale hit
    A2 = A + 1.0
    blk2 = ops.plan_gemm(A2, B, rects, kernel="xla",
                         compute_dtype="float32", pad_cache=pc)[0]
    _assert_close(blk2, _oracle(A2, B)[0:100, 0:60])
    assert pc.misses == 3


def test_pad_cache_fingerprints_bfloat16(rng):
    """bfloat16 (an ml_dtypes type) has no buffer-protocol format code; the
    fingerprint reads its bytes as uint8, and still sees a changed entry."""
    A = rng.standard_normal((64, 48)).astype(jnp.bfloat16)
    fp = ops.PadCache.fingerprint(A)
    assert fp == ops.PadCache.fingerprint(A.copy())
    A[3, 5] += 1
    assert ops.PadCache.fingerprint(A) != fp
    assert ops.PadCache.fingerprint(A.T) is None     # non-contiguous


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Pallas runs compiled on TPU and interpreted on CPU; any other
    backend is refused instead of silently interpreted."""
    assert ops._interpret() is True                  # the CPU test path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


def test_corruption_lands_when_verification_disabled(rng):
    """verify=False must not crash on corrupt_ids, and — like the numpy
    executor — the poisoning lands in the output unchecked."""
    g = cm.GEMM(m=128, n=160, q=128)
    devs = sample_fleet(6, np.random.default_rng(0))
    plan = cm.solve_gemm(g, devs)
    bad = plan.assignments[0].device_id
    a = plan.assignments[0]
    A, B = _ab(rng, g)
    rep_np = executor.execute_plan(g, plan, A, B, devs, corrupt_ids=[bad],
                                   rng=0, verify=False)
    rep_jx = jax_executor.execute_plan_jax(g, plan, A, B, devs,
                                           corrupt_ids=[bad], rng=0,
                                           kernel="xla", verify=False)
    assert rep_np.verified and rep_jx.verified      # nobody checked
    want = _exact(A, B)
    for rep in (rep_np, rep_jx):
        delta = rep.output[a.r0, a.c0] - want[a.r0, a.c0]
        assert abs(delta) > 1.0                     # poison present
    # everything outside the poisoned entry still matches
    mask = np.ones_like(want, bool)
    mask[a.r0, a.c0] = False
    _assert_close(rep_jx.output[mask], want[mask])


def test_pad_cache_detects_inplace_mutation(rng):
    """An in-place operand update between steps (the normal training
    pattern) must re-stage, not silently serve the stale device copy."""
    rt = CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(8, seed=0))
    g = cm.GEMM(m=128, n=192, q=128)
    A, B = _ab(rng, g)
    s1 = rt.execute_step(A, B, gemm=g, backend="jax", kernel="xla")
    _assert_close(s1.output, _oracle(A, B))
    A *= 0.5                                        # same array object
    s2 = rt.execute_step(A, B, gemm=g, backend="jax", kernel="xla")
    assert s2.verified
    _assert_close(s2.output, _oracle(A, B))


def test_jax_executor_session_pad_cache_used(rng):
    """execute_step(backend='jax') routes through the session PadCache."""
    rt = CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(8, seed=0))
    g = cm.GEMM(m=160, n=200, q=150)
    A, B = _ab(rng, g)
    for _ in range(2):
        s = rt.execute_step(A, B, gemm=g, backend="jax", kernel="xla")
    assert rt._pad_cache is not None and rt._pad_cache.hits > 0
    _assert_close(s.output, _oracle(A, B))


# --------------------------------------------------- runtime integration ---

@pytest.fixture
def rt():
    return CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(12, seed=0))


def test_execute_step_backend_dispatch(rt, rng):
    g = cm.GEMM(m=160, n=200, q=150)
    A, B = _ab(rng, g)
    want = _oracle(A, B)
    s_np = rt.execute_step(A, B, gemm=g)
    s_jx = rt.execute_step(A, B, gemm=g, backend="jax", kernel="xla")
    assert s_np.backend == "numpy" and s_jx.backend == "jax"
    assert s_jx.kernel == "xla" and s_jx.phases["kernel"] > 0
    assert s_jx.plan_cached         # both backends share the plan cache
    _assert_close(s_np.output, _exact(A, B), rtol=1e-9)
    _assert_close(s_jx.output, want)
    with pytest.raises(ValueError, match="backend"):
        rt.execute_step(A, B, gemm=g, backend="torch")


def test_execute_step_jax_failure_round_trip(rt, rng):
    g = cm.GEMM(m=192, n=256, q=192)
    plan = rt.plan_gemm(g)
    victim = plan.assignments[0].device_id
    A, B = _ab(rng, g)
    s = rt.execute_step(A, B, gemm=g, backend="jax", fail_ids=[victim])
    assert s.n_recovered > 0 and s.verified
    _assert_close(s.output, _oracle(A, B))


def test_execute_level_runs_dag_level(rt, rng):
    gs = [cm.GEMM(m=128, n=160, q=96), cm.GEMM(m=96, n=128, q=64)]
    pairs = [_ab(rng, g) for g in gs]
    for backend in ("numpy", "jax"):
        rep = rt.execute_level(pairs, gemms=gs, backend=backend,
                               kernel="xla")
        assert rep.verified and len(rep.steps) == 2
        assert rep.predicted_makespan > 0     # engine.price_plan pricing
        for (A, B), s in zip(pairs, rep.steps):
            _assert_close(s.output, _oracle(A, B))
    with pytest.raises(ValueError, match="pairs"):
        rt.execute_level(pairs, gemms=gs[:1])


def test_execute_batch_level_walk(rng):
    """The priced DAG actually runs, level by level, on both backends."""
    from repro.configs.base import get_config
    cfg = get_config("opt-13b").reduced(n_layers=1, vocab_size=256)
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
    rep_np = rt.execute_batch(2, 16, backend="numpy", max_levels=3, seed=5,
                              dispatch="level")
    rep_jx = rt.execute_batch(2, 16, backend="jax", kernel="xla",
                              max_levels=3, seed=5, dispatch="level")
    assert rep_np.verified and rep_jx.verified
    assert rep_np.n_levels == rep_jx.n_levels == 3
    assert rep_np.n_tasks == rep_jx.n_tasks > 0
    assert rep_jx.predicted_gemm_time > 0
    # same seed => same operands => the two backends agree per step
    for lev_np, lev_jx in zip(rep_np.levels, rep_jx.levels):
        for s_np, s_jx in zip(lev_np.steps, lev_jx.steps):
            _assert_close(s_jx.output, s_np.output)
    assert [h["event"] for h in rt.history[-2:]] \
        == ["execute_level", "execute_batch"]
