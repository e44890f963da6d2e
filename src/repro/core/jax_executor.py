"""JAX/Pallas fleet executor: runs a CLEAVE plan's assignment rectangles
through the ``block_gemm`` kernel grid (§3.2 exact-semantics claim, executed
on the accelerator substrate instead of the numpy stand-in).

Each assignment rectangle becomes one sub-GEMM tile.  Rectangles sharing a
row range form a *band* (the grid partition's native structure); bands are
bucketed by MXU-aligned padded height and every bucket runs as ONE batched
kernel launch of its gathered A bands against the shared B
(``kernels.ops.plan_gemm_buckets``), with per-rectangle Freivalds
residuals emitted device-side in the same launch.  Failure, corruption
semantics, and churn recovery follow the numpy executor exactly — same
task order (shared ``executor.build_task_list``), same ``churn.recover``
patch pairs, same PS re-dispatch on a failed check — so the two backends
are drop-in interchangeable behind
``CleaveRuntime.execute_step(backend=...)``.

Dtype policy: inputs are cast to the policy compute dtype (bfloat16 on TPU —
the MXU-native path — float32 elsewhere) and accumulated in float32 inside
the kernel; Freivalds tolerances scale with the compute dtype.  On CPU the
Pallas kernel executes via ``interpret=True`` (correctness parity); pass
``kernel="xla"`` for the compiled host path with identical padding/bucketing
semantics.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Sequence, Tuple, Union

import numpy as np

from repro.core import churn, cost_model as cm
from repro.core.executor import ExecutionReport, build_task_list
from repro.core.seeding import as_rng
from repro.core.spans import span
from repro.core.verify import freivalds


@dataclass(frozen=True)
class DtypePolicy:
    """How the device fleet computes one sub-GEMM tile.

    ``compute_dtype`` is the kernel input dtype (MXU operand precision);
    accumulation is always float32 (``preferred_element_type`` in the
    kernel).  ``eps`` is the compute dtype's unit roundoff and
    ``freivalds_c`` a safety factor: the per-block Freivalds tolerance is
    ``c * eps * sqrt(n / area)`` relative to the |r|·|C|·|s| scale, which
    keeps a constant margin over the probabilistic rounding residual
    (~sqrt(area·n)·eps·|C|) for every rectangle shape — tight slivers and
    wide blocks alike — while O(1) poisoning stays detectable under the
    f32 policy (bf16 rounding noise genuinely swamps a minimum-magnitude
    single-entry corruption on large blocks; that is physics, not a bug).
    """
    name: str
    compute_dtype: str
    eps: float
    freivalds_c: float

    def freivalds_rtol(self, n: int, area: int) -> float:
        return self.freivalds_c * self.eps * float(
            np.sqrt(max(n, 1) / max(area, 1)))


POLICIES = {
    # f32 compute / f32 accumulate: the CPU-parity and equivalence-suite
    # policy (matches the numpy/f64 executor to <=1e-5 relative)
    "f32": DtypePolicy(name="f32", compute_dtype="float32",
                       eps=1.2e-7, freivalds_c=16.0),
    # bf16 compute / f32 accumulate: the TPU MXU-native policy
    "bf16": DtypePolicy(name="bf16", compute_dtype="bfloat16",
                        eps=7.8e-3, freivalds_c=32.0),
}


def default_policy() -> DtypePolicy:
    import jax
    return POLICIES["bf16" if jax.default_backend() == "tpu" else "f32"]


def get_policy(policy: Union[str, DtypePolicy, None]) -> DtypePolicy:
    if policy is None:
        return default_policy()
    if isinstance(policy, DtypePolicy):
        return policy
    if policy not in POLICIES:
        raise ValueError(f"unknown dtype policy {policy!r}; "
                         f"known: {sorted(POLICIES)} or a DtypePolicy")
    return POLICIES[policy]


@dataclass
class JaxExecutionReport(ExecutionReport):
    """ExecutionReport plus the accelerator substrate's accounting."""
    backend: str = "jax"
    kernel: str = "xla"            # 'pallas' | 'xla' (resolved)
    policy: str = "f32"
    exec_time: float = 0.0         # kernel + gather/scatter wall-clock
    padded_flops: float = 0.0      # GEMM FLOPs the bucket launches ran,
    #                                padding included (2 bands pm nk qk)


def _redispatch(Ab: np.ndarray, Bb: np.ndarray,
                pol: DtypePolicy) -> np.ndarray:
    """Clean recompute of one tile under the policy dtype (the PS
    re-dispatch after a failed Freivalds check)."""
    import jax.numpy as jnp
    return np.asarray(jnp.einsum(
        "mk,kq->mq", jnp.asarray(Ab, pol.compute_dtype),
        jnp.asarray(Bb, pol.compute_dtype),
        preferred_element_type=jnp.float32), np.float32)


def execute_plan_jax_deferred(
        gemm: cm.GEMM, plan: cm.Plan, A: np.ndarray,
        B: np.ndarray, devices: cm.Fleetlike,
        fail_ids: Sequence[int] = (),
        corrupt_ids: Sequence[int] = (),
        rng: Union[np.random.Generator, int, None] = None,
        verify: bool = True,
        policy: Union[str, DtypePolicy, None] = None,
        kernel: str = "auto",
        block: int = 128,
        pad_cache=None
        ) -> Tuple[JaxExecutionReport, Callable[[], List[tuple]]]:
    """Split-phase :func:`execute_plan_jax`: the compute phase runs the
    bucket launches (which emit the device-side Freivalds residuals in the
    same launch) and scatters the blocks; the returned ``finalize`` closure
    reduces the residuals against the policy tolerance, confirms flagged
    blocks with the host oracle, and re-dispatches genuine corruption —
    updating ``report.verified`` and ``report.phases["verify"]`` and
    returning the corrected rects.  Calling ``finalize()`` immediately matches
    :func:`execute_plan_jax`; the dataflow dispatcher overlaps it with the
    next node's gathers instead (verification of node *k* behind node
    *k+1*'s staging).

    Semantics mirror :func:`repro.core.executor.execute_plan` (the two
    backends share :func:`repro.core.executor.build_task_list`, so task
    order cannot drift): devices in ``fail_ids`` vanish before uploading
    (their rectangles are re-solved via ``churn.recover`` and executed by
    survivors), devices in ``corrupt_ids`` return poisoned blocks that
    Freivalds verification must catch (the PS then re-dispatches the tile).

    Verification runs device-side: every bucket launch emits per-block
    Freivalds residuals alongside the blocks (three extra batched matvecs,
    see ``kernels.ops._bucket_gemm_verified``), the executor reduces them
    to a boolean pass-vector against the dtype policy's per-block
    tolerance, and only flagged blocks fall back to the host
    :func:`~repro.core.verify.freivalds` oracle (and, when the oracle
    confirms the failure, a clean PS re-dispatch).  The output scatter is
    one fancy-indexed write per bucket instead of a per-task Python loop.

    ``A`` and ``B`` may be host arrays or device arrays (``jax.Array``).
    A device operand stays on the device up to the bucket launch (padded
    there, ``kernels.ops._staged_pad``); of it, only the slices of blocks
    that the device residuals flag are fetched to the host, for the
    oracle and the re-dispatch.  ``report.host_operand_bytes`` counts the
    operand bytes read on the host.

    ``kernel`` selects the compiled substrate
    (see :func:`repro.kernels.ops.resolve_plan_kernel`); ``policy`` the
    compute dtype; ``pad_cache`` an optional ``kernels.ops.PadCache``
    reusing the device-resident padded copies of host operands across
    calls.  Prefer driving this through
    ``CleaveRuntime.execute_step(backend="jax")``.
    """
    import jax

    from repro.kernels import ops

    pol = get_policy(policy)
    kernel = ops.resolve_plan_kernel(kernel)
    rng = as_rng(rng)
    m, q = gemm.m, gemm.q
    assert A.shape == (m, gemm.n) and B.shape == (gemm.n, q)
    corrupt = set(corrupt_ids)
    a_dev, b_dev = isinstance(A, jax.Array), isinstance(B, jax.Array)
    phases: dict = {}

    with span("cleave.fleet.tasks", phases):
        tasks, recovery = build_task_list(gemm, plan, devices, fail_ids)
        n_rec = sum(1 for t in tasks if t.is_recovery)
        rects = [(t.r0, t.r1, t.c0, t.c1) for t in tasks]
        corrupt_mask = np.fromiter((t.device_id in corrupt for t in tasks),
                                   np.float32, count=len(tasks))

    # ---- one batched (compute + verify) pass per padded-shape bucket -----
    t0 = time.perf_counter()
    seed = int(rng.integers(0, 2 ** 31 - 1)) if verify else None
    runs = ops.plan_gemm_buckets(A, B, rects, block=block, kernel=kernel,
                                 compute_dtype=pol.compute_dtype,
                                 verify_seed=seed, corrupt=corrupt_mask,
                                 pad_cache=pad_cache, phases=phases)

    with span("cleave.fleet.scatter", phases):
        C = np.zeros((m, q), np.float32)
        filled = np.zeros((m, q), bool)
        for run in runs:
            # vectorized scatter: each band bulk-writes the contiguous runs
            # of its rects' column-window union (a grid partition's bands
            # tile the width, so this is one slice write per band)
            Gb = len(run.band_r0s)
            cover = np.zeros((Gb, q + 1), np.int32)
            np.add.at(cover, (run.bidx, run.c0s), 1)
            np.add.at(cover, (run.bidx, run.c1s), -1)
            cover = np.cumsum(cover[:, :q], axis=1) > 0
            for b in range(Gb):
                r0, h = int(run.band_r0s[b]), int(run.band_hs[b])
                edges = np.flatnonzero(np.diff(cover[b].astype(np.int8)))
                bounds = np.concatenate(
                    ([0] if cover[b, 0] else [], edges + 1,
                     [q] if cover[b, -1] else [])).astype(np.int64)
                for s0, s1 in bounds.reshape(-1, 2):
                    C[r0:r0 + h, s0:s1] = run.out[b, :h, s0:s1]
                    filled[r0:r0 + h, s0:s1] = True
            if not verify:
                # poisoning still lands in the output (nobody checks it);
                # injected post-scatter into the writable C, same
                # blk[0,0] += 1 + |blk[0,0]| form as the numpy executor
                for g in np.nonzero(corrupt_mask[run.idx])[0]:
                    r0, c0 = rects[run.idx[g]][0], rects[run.idx[g]][2]
                    C[r0, c0] += 1.0 + abs(C[r0, c0])
        assert filled.all(), "coverage violated"
        assert sum(t.area for t in tasks) == m * q, "overlapping assignment"
    exec_time = time.perf_counter() - t0

    report = JaxExecutionReport(
        output=C, verified=True, n_tasks=len(tasks), n_recovered=n_rec,
        recovery=recovery, phases=phases, backend="jax", kernel=kernel,
        policy=pol.name, exec_time=exec_time,
        padded_flops=sum(run.padded_flops for run in runs),
        host_operand_bytes=(0 if a_dev else A.nbytes)
        + (0 if b_dev else B.nbytes))

    def finalize() -> List[tuple]:
        corrected: List[tuple] = []
        if not verify:
            return corrected
        with span("cleave.fleet.verify", report.phases):
            flagged = []
            for run in runs:
                hs = run.band_hs.astype(np.int64)[run.bidx]
                ws = (run.c1s - run.c0s).astype(np.int64)
                rtols = pol.freivalds_c * pol.eps * np.sqrt(
                    max(gemm.n, 1) / np.maximum(hs * ws, 1))
                ok = np.all(
                    np.abs(run.lhs - run.rhs)
                    <= rtols[:, None] * np.abs(run.rhs)
                    + (rtols * (run.scale + 1e-30))[:, None], axis=1)
                flagged += [(run, g, float(rtols[g]))
                            for g in np.nonzero(~ok)[0]]
        if not flagged:
            return corrected
        # the device-side residual flagged these blocks: the host oracle
        # reads their operand slices, fetched where the operands live on
        # the device
        slices = []
        for run, g, _ in flagged:
            r0, r1, c0, c1 = rects[run.idx[g]]
            slices.append((A[r0:r1], B[:, c0:c1]))
        if a_dev or b_dev:
            with span("cleave.fleet.d2h", report.phases):
                slices = [(np.asarray(Ab), np.asarray(Bb))
                          for Ab, Bb in slices]
            report.host_operand_bytes += sum(
                a_dev * Ab.nbytes + b_dev * Bb.nbytes for Ab, Bb in slices)
        with span("cleave.fleet.verify", report.phases):
            for (run, g, rtol), (Ab, Bb) in zip(flagged, slices):
                # confirm with the host oracle, then model the PS
                # re-dispatch to a clean device (same dtype policy) for
                # genuine corruption
                r0, r1, c0, c1 = rects[run.idx[g]]
                if freivalds(Ab, Bb, run.block(g), rng, rtol=rtol):
                    continue
                report.verified = False
                C[r0:r1, c0:c1] = _redispatch(Ab, Bb, pol)
                corrected.append((r0, r1, c0, c1))
        return corrected

    return report, finalize


def execute_plan_jax(gemm: cm.GEMM, plan: cm.Plan, A: np.ndarray,
                     B: np.ndarray, devices: cm.Fleetlike,
                     fail_ids: Sequence[int] = (),
                     corrupt_ids: Sequence[int] = (),
                     rng: Union[np.random.Generator, int, None] = None,
                     verify: bool = True,
                     policy: Union[str, DtypePolicy, None] = None,
                     kernel: str = "auto",
                     block: int = 128,
                     pad_cache=None) -> JaxExecutionReport:
    """Execute every assignment rectangle on the JAX backend, verifying
    inline (compute phase + immediate finalize — see
    :func:`execute_plan_jax_deferred` for the split-phase form the dataflow
    dispatcher overlaps)."""
    report, finalize = execute_plan_jax_deferred(
        gemm, plan, A, B, devices, fail_ids=fail_ids,
        corrupt_ids=corrupt_ids, rng=rng, verify=verify, policy=policy,
        kernel=kernel, block=block, pad_cache=pad_cache)
    finalize()
    report.exec_time += report.phases.get("verify", 0.0)
    return report
