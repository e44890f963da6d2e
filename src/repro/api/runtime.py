"""`CleaveRuntime`: the unified plan → execute → recover → stream session.

One object owns what every caller used to re-wire by hand (§3.2, §4):

* DAG tracing (``build_dag``) with per-(batch, seq) memoization,
* scheduling (``scheduler.schedule``) against a **runtime-owned,
  fleet-signature-keyed plan cache**, so repeated steps and churn re-plans
  reuse solved shapes (the paper's cold-start amortization, Table 7),
* numerical execution with failure injection + Freivalds verification
  (``executor.execute_plan``),
* churn recovery (``churn.recover``) that *patches* cached plans instead of
  re-solving them from scratch (§4.2 incremental re-solve),
* streaming latency profiling and pluggable straggler mitigation
  (``core.streaming`` via a ``mitigation=`` policy),
* unicast/broadcast accounting as a strategy object shared with the
  simulator,
* timeline simulation (``simulate``): the batch replayed on the
  discrete-event fleet engine with injectable fail/join/slowdown events,
  optional Pareto stage jitter, and PS link contention
  (``backend="analytic"`` stays the closed-form fast path; the event
  backend reproduces it exactly in the deterministic case).

Typical session::

    rt = CleaveRuntime(arch="opt-13b", fleet=Fleet.sample(256, seed=0),
                       accounting="broadcast")
    report = rt.plan(batch=128, seq=1024)     # cold solve
    report = rt.plan(batch=128, seq=1024)     # cache hit, ~free
    step = rt.execute_step(A, B, fail_ids=[7])   # survives the failure
    rt.on_failure([7])                        # evict + patch cached plans
    step = rt.execute_step(A, B)              # warm re-plan, exact output
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.configs.base import get_config
from repro.core import churn, cost_model as cm, executor
from repro.core.gemm_dag import GemmDag, build_dag
from repro.core.scheduler import (SchedulePlan, plan_shape_key,
                                  reprice_plan, schedule, solve_level_gemm)
from repro.core.spans import span
from repro.api.accounting import (AccountingResult, AccountingStrategy,
                                  get_accounting)
from repro.api.fleet import Fleet
from repro.api.mitigation import (MitigationPolicy, MitigationReport,
                                  get_mitigation)
from repro.sim.events import TimelineEvent, TimelineReport


# ------------------------------------------------------------------- types --

@dataclass(frozen=True)
class PlanRequest:
    """What to plan: one training (or forward-only) batch of the session's
    architecture.  Hashable — also the runtime's DAG-cache key."""
    batch: int
    seq: int
    attention_scores: str = "ps"
    backward: bool = True
    lm_head: bool = True
    heterogeneity_aware: bool = True


@dataclass
class PlanReport:
    """Result of :meth:`CleaveRuntime.plan`: the priced batch schedule."""
    request: PlanRequest
    accounting: str
    batch_time: float
    gemm_time: float
    opt_tail: float
    per_device_comm: float
    per_device_mem: float
    schedule: SchedulePlan
    fleet_signature: str
    solve_time: float           # wall-clock of this plan() call
    cache_hits: int             # unique shapes served from the plan cache
    cache_misses: int           # unique shapes solved cold this call
    mitigation: Optional[MitigationReport] = None

    @property
    def cached(self) -> bool:
        return self.cache_misses == 0


@dataclass
class StepReport:
    """Result of :meth:`CleaveRuntime.execute_step`: one GEMM executed
    numerically on the fleet (exact-semantics claim, §3.2)."""
    gemm: cm.GEMM
    plan: cm.Plan
    output: np.ndarray
    verified: bool
    n_tasks: int
    n_recovered: int
    recovery: Optional[churn.RecoveryResult]
    exec_time: float
    plan_cached: bool
    backend: str = "numpy"      # 'numpy' | 'jax'
    kernel: str = ""            # jax backend: resolved 'pallas' | 'xla'
    # host seconds per span (``core.spans`` short names): ``plan`` here,
    # then the executor's (``ExecutionReport.phases``)
    phases: Dict[str, float] = field(default_factory=dict)
    padded_flops: float = 0.0   # jax backend: GEMM FLOPs launched, padding
    #                             included (``JaxExecutionReport``)
    host_operand_bytes: int = 0  # operand bytes read on the host
    #                              (``ExecutionReport.host_operand_bytes``)


@dataclass
class LevelReport:
    """Result of :meth:`CleaveRuntime.execute_level`: one GemmDag level —
    mutually independent GEMMs — executed on the fleet backend, with the
    event engine's plan pricing as the predicted level latency."""
    steps: List[StepReport]
    backend: str
    level_time: float           # wall-clock of executing the level
    predicted_makespan: float   # engine.price_plan max over the level
    verified: bool
    n_tasks: int
    n_recovered: int

    @property
    def outputs(self) -> List[np.ndarray]:
        return [s.output for s in self.steps]


@dataclass
class BatchExecuteReport:
    """Result of :meth:`CleaveRuntime.execute_batch`: the batch's GemmDag
    executed for real — level by level (``dispatch="level"``, §3.2's
    barrier walk) or readiness-driven (``dispatch="dataflow"``, the
    default: a node launches as soon as its producers complete, operand
    staging is prefetched behind the running compute, and Freivalds
    verification overlaps downstream gathers).  Either way ``levels``
    groups the per-GEMM steps by DAG level, so level-shaped consumers read
    the same report; under dataflow a level's ``level_time`` is the summed
    step exec time attributed to that level, not a measured barrier."""
    request: PlanRequest
    backend: str
    levels: List[LevelReport]
    wall_time: float
    predicted_gemm_time: float  # sum of engine-priced level makespans (Eq. 1)
    verified: bool
    n_tasks: int
    n_recovered: int
    dispatch: str = "level"     # 'level' | 'dataflow'
    # engine.price_dataflow critical path through the ready set — the
    # barrier-free analog of predicted_gemm_time (dataflow dispatch only)
    predicted_overlap_time: Optional[float] = None
    n_redispatched: int = 0     # dependents re-run after a failed verify

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    @property
    def steps(self) -> List[StepReport]:
        return [s for lev in self.levels for s in lev.steps]


@dataclass
class ChurnReport:
    """Result of :meth:`CleaveRuntime.on_failure`: the fleet shrank and the
    plan cache was incrementally patched (§4.2)."""
    failed_ids: List[int]
    n_survivors: int
    n_plans_patched: int        # plans with orphaned shards, re-solved
    #                             incrementally over the survivors
    n_plans_carried: int        # plans untouched by the failure, re-keyed
    n_plans_dropped: int        # cached plans that must re-solve cold
    recovery_time: float        # worst patch-schedule makespan
    recomputed_fraction: float  # worst recomputed output share
    solve_time: float           # wall-clock of the incremental patching
    fleet_signature: str


@dataclass
class StreamReport:
    """Result of :meth:`CleaveRuntime.stream_profile`: the three-stage
    DL/compute/UL pipeline (Eq. 9') with optional Pareto jitter and the
    session's mitigation policy applied."""
    serial_time: float
    pipelined_time: float
    jittered_time: float
    mitigation: MitigationReport

    @property
    def overlap_speedup(self) -> float:
        return self.serial_time / max(self.pipelined_time, 1e-12)


# ----------------------------------------------------------------- runtime --

class CleaveRuntime:
    """The canonical CLEAVE entry surface (see module docstring)."""

    def __init__(self, arch: Union[str, object] = "opt-13b",
                 fleet: Optional[Fleet] = None, *,
                 accounting: Union[str, AccountingStrategy] = "unicast",
                 mitigation: Union[str, MitigationPolicy, None] = "none",
                 ps: Optional[cm.PSConfig] = None,
                 attention_scores: str = "ps",
                 heterogeneity_aware: bool = True,
                 seed: int = 0):
        self.cfg = get_config(arch) if isinstance(arch, str) else arch
        self.fleet = fleet if fleet is not None else Fleet.sample(256,
                                                                  seed=seed)
        self.accounting = get_accounting(accounting)
        self.mitigation = get_mitigation(mitigation)
        self.ps = ps or cm.PSConfig()
        self.attention_scores = attention_scores
        self.heterogeneity_aware = heterogeneity_aware
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        # compact event log (dicts): never holds outputs or plans, so a
        # long-running session does not pin per-step matrices
        self.history: List[dict] = []
        self._dag_cache: Dict[PlanRequest, GemmDag] = {}
        # (fleet_signature, heterogeneity_aware) -> {shape_key: cm.Plan}
        self._plan_caches: Dict[Tuple[str, bool], Dict[tuple, cm.Plan]] = {}
        # (request, fleet_signature) -> solved SchedulePlan
        self._sched_cache: Dict[Tuple[PlanRequest, str], SchedulePlan] = {}
        # device-resident padded-operand cache for the jax step loop
        # (kernels.ops.PadCache, created lazily so numpy-only sessions
        # never import jax)
        self._pad_cache = None
        # lazily-built PS-centric training sessions, keyed by their
        # executor options so repeated train_step() calls share warm plan
        # caches and per-run step counters (repro.train_loop)
        self._train_sessions: Dict[tuple, object] = {}

    # ---------------------------------------------------------------- plan --

    def plan(self, batch: Optional[int] = None, seq: Optional[int] = None,
             *, request: Optional[PlanRequest] = None) -> PlanReport:
        """Solve (or warm-load) the batch schedule for the session fleet."""
        if request is None:
            if batch is None or seq is None:
                raise ValueError("plan() needs batch+seq or a PlanRequest")
            request = PlanRequest(
                batch=batch, seq=seq,
                attention_scores=self.attention_scores,
                heterogeneity_aware=self.heterogeneity_aware)
        dag = self._dag(request)
        cache = self._cache(request.heterogeneity_aware)
        sched_key = (request, self.fleet.signature())
        t0 = time.perf_counter()
        sp = self._sched_cache.get(sched_key)
        if sp is not None:
            # repeated step with an unchanged fleet: the solved schedule is
            # reused outright (Table 7 cold-start amortization)
            hits, misses = len(sp.plans_by_shape), 0
        else:
            shapes = {plan_shape_key(g) + (g.count,) for g in dag.gemms}
            hits = sum(1 for k in shapes if k in cache)
            misses = len(shapes) - hits
            sp = schedule(dag, self.fleet.table(), ps=self.ps,
                          heterogeneity_aware=request.heterogeneity_aware,
                          plan_cache=cache)
            self._sched_cache[sched_key] = sp
        solve_time = time.perf_counter() - t0
        acc = self.accounting.apply(dag, sp)
        report = PlanReport(
            request=request, accounting=self.accounting.name,
            batch_time=acc.batch_time, gemm_time=acc.gemm_time,
            opt_tail=acc.opt_tail, per_device_comm=acc.per_device_comm,
            per_device_mem=acc.per_device_mem, schedule=sp,
            fleet_signature=self.fleet.signature(), solve_time=solve_time,
            cache_hits=hits, cache_misses=misses,
            mitigation=self.mitigation.mitigate(acc.batch_time))
        self.history.append({
            "event": "plan", "batch": request.batch, "seq": request.seq,
            "batch_time": report.batch_time,
            "solve_time": report.solve_time, "cached": report.cached})
        return report

    def plan_gemm(self, gemm: cm.GEMM) -> cm.Plan:
        """Solve (or warm-load) one GEMM's sub-task plan.  Shares the shape
        cache with :meth:`plan`, so a GEMM appearing in a planned DAG is
        already warm."""
        plan, _ = self._solve_gemm(gemm)
        return plan

    # ------------------------------------------------------------- execute --

    def execute_step(self, A: np.ndarray, B: np.ndarray, *,
                     gemm: Optional[cm.GEMM] = None,
                     fail_ids: Sequence[int] = (),
                     corrupt_ids: Sequence[int] = (),
                     verify: bool = True,
                     backend: str = "numpy",
                     dtype_policy=None,
                     kernel: str = "auto") -> StepReport:
        """Numerically execute one GEMM's plan on the fleet.  Devices in
        ``fail_ids`` vanish mid-level (in-flight recovery via
        ``churn.recover``); ``corrupt_ids`` return poisoned blocks that
        Freivalds verification must catch.  Uses the session RNG, so a
        fixed-seed session is bit-reproducible.

        ``backend="numpy"`` (default) is the float64 host stand-in;
        ``backend="jax"`` runs the same tile decomposition through the
        Pallas ``block_gemm`` kernel grid (``core.jax_executor``) with
        MXU-aligned padding and a bf16-compute/f32-accumulate dtype policy
        on TPU (f32/f32 elsewhere — ``interpret=True`` parity on CPU).
        ``dtype_policy`` / ``kernel`` pass through to the jax backend,
        which also takes device arrays (``jax.Array``): they stay on the
        device up to the kernel launch, and the session ``PadCache``
        serves host operands only.  ``StepReport.host_operand_bytes``
        counts the operand bytes read on the host."""
        if gemm is None:
            gemm = cm.GEMM(m=A.shape[0], n=A.shape[1], q=B.shape[1])
        phases: Dict[str, float] = {}
        with span("cleave.fleet.plan", phases):
            plan, cached = self._solve_gemm(gemm)
        report = self._execute_one(gemm, plan, cached, A, B,
                                   fail_ids=fail_ids,
                                   corrupt_ids=corrupt_ids, verify=verify,
                                   backend=backend,
                                   dtype_policy=dtype_policy, kernel=kernel,
                                   phases=phases)
        self.history.append({
            "event": "execute_step", "shape": (gemm.m, gemm.n, gemm.q),
            "backend": report.backend,
            "verified": report.verified, "n_tasks": report.n_tasks,
            "n_recovered": report.n_recovered, "plan_cached": cached})
        return report

    def _execute_one(self, gemm: cm.GEMM, plan: cm.Plan, cached: bool,
                     A: np.ndarray, B: np.ndarray, *,
                     fail_ids: Sequence[int], corrupt_ids: Sequence[int],
                     verify: bool, backend: str, dtype_policy,
                     kernel: str,
                     phases: Optional[Dict[str, float]] = None
                     ) -> StepReport:
        t0 = time.perf_counter()
        if backend == "numpy":
            rep = executor.execute_plan(gemm, plan, A, B,
                                        self.fleet.devices,
                                        fail_ids=fail_ids,
                                        corrupt_ids=corrupt_ids,
                                        rng=self.rng, verify=verify)
            kern, padded = "", 0.0
        elif backend == "jax":
            from repro.core import jax_executor
            if self._pad_cache is None:
                from repro.kernels.ops import PadCache
                self._pad_cache = PadCache()
            rep = jax_executor.execute_plan_jax(
                gemm, plan, A, B, self.fleet.table(), fail_ids=fail_ids,
                corrupt_ids=corrupt_ids, rng=self.rng, verify=verify,
                policy=dtype_policy, kernel=kernel,
                pad_cache=self._pad_cache)
            kern, padded = rep.kernel, rep.padded_flops
        else:
            raise ValueError(f"unknown executor backend {backend!r}; "
                             "expected 'numpy' or 'jax'")
        exec_time = time.perf_counter() - t0
        phases = {} if phases is None else phases
        phases.update(rep.phases)
        return StepReport(
            gemm=gemm, plan=plan, output=rep.output, verified=rep.verified,
            n_tasks=rep.n_tasks, n_recovered=rep.n_recovered,
            recovery=rep.recovery, exec_time=exec_time,
            plan_cached=cached, backend=backend, kernel=kern,
            phases=phases, padded_flops=padded,
            host_operand_bytes=rep.host_operand_bytes)

    def execute_step_deferred(self, A: np.ndarray, B: np.ndarray, *,
                              gemm: Optional[cm.GEMM] = None,
                              fail_ids: Sequence[int] = (),
                              corrupt_ids: Sequence[int] = (),
                              verify: bool = True,
                              backend: str = "numpy",
                              dtype_policy=None, kernel: str = "auto",
                              rng: Optional[np.random.Generator] = None,
                              staged=None):
        """Split-phase :meth:`execute_step` for dataflow dispatch: returns
        ``(StepReport, finalize)`` where the report carries the compute
        phase only (block GEMMs + scatter; ``exec_time`` excludes
        verification) and ``finalize()`` runs the deferred Freivalds
        checks — correcting any failed block in place, updating the
        report's ``verified``/``n_recovered``, and returning the corrected
        rects (truthy ⇒ dependents computed against a later-corrected
        block must be re-dispatched).  Calling ``finalize()`` immediately
        matches :meth:`execute_step`.

        ``rng`` seeds the Freivalds draws; the dataflow dispatcher passes a
        per-node child generator so overlapped verification cannot race the
        session stream (default: a child split off ``self.rng``)."""
        if gemm is None:
            gemm = cm.GEMM(m=A.shape[0], n=A.shape[1], q=B.shape[1])
        phases: Dict[str, float] = {}
        with span("cleave.fleet.plan", phases):
            plan, cached = self._solve_gemm(gemm)
        step, fin = self._execute_one_deferred(
            gemm, plan, cached, A, B, fail_ids=fail_ids,
            corrupt_ids=corrupt_ids, verify=verify, backend=backend,
            dtype_policy=dtype_policy, kernel=kernel, rng=rng,
            staged=staged, phases=phases)
        self.history.append({
            "event": "execute_step", "shape": (gemm.m, gemm.n, gemm.q),
            "backend": step.backend, "deferred": True,
            "verified": step.verified, "n_tasks": step.n_tasks,
            "n_recovered": step.n_recovered, "plan_cached": cached})
        return step, fin

    def _execute_one_deferred(self, gemm: cm.GEMM, plan: cm.Plan,
                              cached: bool, A: np.ndarray, B: np.ndarray,
                              *, fail_ids: Sequence[int],
                              corrupt_ids: Sequence[int], verify: bool,
                              backend: str, dtype_policy, kernel: str,
                              rng: Optional[np.random.Generator] = None,
                              staged=None,
                              phases: Optional[Dict[str, float]] = None):
        """Split-phase :meth:`_execute_one`.  The returned StepReport's
        ``exec_time`` and ``phases`` cover the compute phase only;
        ``finalize()`` (thread-safe against other nodes' compute) syncs the
        verification outcome back into the report and returns the
        corrected rects."""
        if rng is None:
            # never hand the session generator to overlapped verification:
            # a finalize racing the next node's draw would break seeded
            # reproducibility of everything downstream
            rng = np.random.default_rng(self.rng.integers(2 ** 63 - 1))
        t0 = time.perf_counter()
        if backend == "numpy":
            rep, fin = executor.execute_plan_deferred(
                gemm, plan, A, B, self.fleet.devices, fail_ids=fail_ids,
                corrupt_ids=corrupt_ids, rng=rng, verify=verify,
                staged=staged)
            kern, padded = "", 0.0
        elif backend == "jax":
            from repro.core import jax_executor
            if self._pad_cache is None:
                from repro.kernels.ops import PadCache
                self._pad_cache = PadCache()
            rep, fin = jax_executor.execute_plan_jax_deferred(
                gemm, plan, A, B, self.fleet.table(), fail_ids=fail_ids,
                corrupt_ids=corrupt_ids, rng=rng, verify=verify,
                policy=dtype_policy, kernel=kernel,
                pad_cache=self._pad_cache)
            kern, padded = rep.kernel, rep.padded_flops
        else:
            raise ValueError(f"unknown executor backend {backend!r}; "
                             "expected 'numpy' or 'jax'")
        exec_time = time.perf_counter() - t0
        # a copy: the deferred finalize adds its verify phase to the
        # executor's own dict, on whichever thread runs it
        phases = {} if phases is None else phases
        phases.update(rep.phases)
        step = StepReport(
            gemm=gemm, plan=plan, output=rep.output, verified=rep.verified,
            n_tasks=rep.n_tasks, n_recovered=rep.n_recovered,
            recovery=rep.recovery, exec_time=exec_time,
            plan_cached=cached, backend=backend, kernel=kern,
            phases=phases, padded_flops=padded,
            host_operand_bytes=rep.host_operand_bytes)

        def finalize():
            corrected = fin()
            step.verified = rep.verified
            step.n_recovered = rep.n_recovered
            step.host_operand_bytes = rep.host_operand_bytes
            return corrected

        return step, finalize

    def execute_level(self, pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
                      *, gemms: Optional[Sequence[cm.GEMM]] = None,
                      fail_ids: Sequence[int] = (),
                      corrupt_ids: Sequence[int] = (),
                      verify: bool = True, backend: str = "numpy",
                      dtype_policy=None, kernel: str = "auto",
                      heterogeneity_aware: Optional[bool] = None
                      ) -> LevelReport:
        """Execute one GemmDag level: ``pairs`` is the level's ``(A, B)``
        operand list (mutually independent GEMMs, Eq. 1).  Each GEMM's plan
        is solved (or warm-loaded) from the session cache and run on the
        chosen backend; the report carries the event engine's
        ``price_plan`` level makespan next to the measured wall time, so
        the predicted and executed schedule walk the same shapes.
        ``heterogeneity_aware`` overrides the session flag (``None``), so
        an ablation request executes the plans it priced."""
        from repro.sim.engine import price_plan
        if gemms is None:
            gemms = [cm.GEMM(m=A.shape[0], n=A.shape[1], q=B.shape[1])
                     for A, B in pairs]
        if len(gemms) != len(pairs):
            raise ValueError(f"{len(pairs)} operand pairs for "
                             f"{len(gemms)} GEMMs")
        t0 = time.perf_counter()
        steps: List[StepReport] = []
        predicted = 0.0
        for g, (A, B) in zip(gemms, pairs):
            plan, cached = self._solve_gemm(
                g, heterogeneity_aware=heterogeneity_aware)
            predicted = max(predicted, price_plan(g, plan,
                                                  self.fleet.devices))
            steps.append(self._execute_one(
                g, plan, cached, A, B, fail_ids=fail_ids,
                corrupt_ids=corrupt_ids, verify=verify, backend=backend,
                dtype_policy=dtype_policy, kernel=kernel))
        report = LevelReport(
            steps=steps, backend=backend,
            level_time=time.perf_counter() - t0,
            predicted_makespan=predicted,
            verified=all(s.verified for s in steps),
            n_tasks=sum(s.n_tasks for s in steps),
            n_recovered=sum(s.n_recovered for s in steps))
        self.history.append({
            "event": "execute_level", "backend": backend,
            "n_gemms": len(steps), "n_tasks": report.n_tasks,
            "n_recovered": report.n_recovered,
            "verified": report.verified})
        return report

    def execute_batch(self, batch: Optional[int] = None,
                      seq: Optional[int] = None, *,
                      request: Optional[PlanRequest] = None,
                      inputs=None, max_levels: Optional[int] = None,
                      verify: bool = True, backend: str = "numpy",
                      dtype_policy=None, kernel: str = "auto",
                      seed: Optional[int] = None,
                      dispatch: str = "dataflow",
                      fail_ids: Sequence[int] = (),
                      corrupt_ids: Sequence[int] = (),
                      dataflow_workers: Optional[int] = None
                      ) -> BatchExecuteReport:
        """Execute the batch's GemmDag for real on the chosen backend — the
        schedule the session prices is the schedule that runs.

        ``dispatch="dataflow"`` (default) runs the readiness-driven walk
        (``core.dataflow``): each GEMM launches as soon as its producers
        complete, operand staging prefetches behind the running compute,
        and Freivalds verification of node *k* overlaps node *k+1*'s
        gathers (a failed check corrects the block and re-dispatches only
        the dependents already in flight).  ``dispatch="level"`` is the
        §3.2 barrier walk — the oracle the dataflow path is tested
        against; outputs are identical for a fixed seed.

        ``inputs`` maps a GEMM to its ``(A, B)`` operands (default: seeded
        standard-normal float32 — a numerics walk, not trained weights;
        operands are drawn in level order on both dispatch paths, so the
        walks see the same matrices); count>1 GEMMs execute one
        representative instance.  ``max_levels`` bounds the walk for
        smoke-level budgets.  ``fail_ids`` / ``corrupt_ids`` inject device
        failure / poisoned blocks into every executed GEMM."""
        if request is None:
            if batch is None or seq is None:
                raise ValueError("execute_batch() needs batch+seq or a "
                                 "PlanRequest")
            request = PlanRequest(
                batch=batch, seq=seq,
                attention_scores=self.attention_scores,
                heterogeneity_aware=self.heterogeneity_aware)
        if dispatch not in ("level", "dataflow"):
            raise ValueError(f"unknown dispatch {dispatch!r}; "
                             "expected 'level' or 'dataflow'")
        dag = self._dag(request)
        in_rng = np.random.default_rng(self.seed if seed is None else seed)
        if inputs is None:
            def inputs(g: cm.GEMM):
                A = in_rng.standard_normal((g.m, g.n)).astype(np.float32)
                B = in_rng.standard_normal((g.n, g.q)).astype(np.float32)
                return A, B
        t0 = time.perf_counter()
        if dispatch == "level":
            levels: List[LevelReport] = []
            for li, level in enumerate(dag.levels()):
                if max_levels is not None and li >= max_levels:
                    break
                pairs = [inputs(g) for g in level]
                levels.append(self.execute_level(
                    pairs, gemms=level, verify=verify, backend=backend,
                    fail_ids=fail_ids, corrupt_ids=corrupt_ids,
                    dtype_policy=dtype_policy, kernel=kernel,
                    heterogeneity_aware=request.heterogeneity_aware))
            overlap_time, n_redispatched = None, 0
        else:
            levels, overlap_time, n_redispatched = self._execute_dataflow(
                dag, inputs, max_levels=max_levels, verify=verify,
                backend=backend, dtype_policy=dtype_policy, kernel=kernel,
                heterogeneity_aware=request.heterogeneity_aware,
                fail_ids=fail_ids, corrupt_ids=corrupt_ids,
                max_workers=dataflow_workers)
        report = BatchExecuteReport(
            request=request, backend=backend, levels=levels,
            wall_time=time.perf_counter() - t0,
            predicted_gemm_time=float(sum(l.predicted_makespan
                                          for l in levels)),
            verified=all(l.verified for l in levels),
            n_tasks=sum(l.n_tasks for l in levels),
            n_recovered=sum(l.n_recovered for l in levels),
            dispatch=dispatch, predicted_overlap_time=overlap_time,
            n_redispatched=n_redispatched)
        self.history.append({
            "event": "execute_batch", "backend": backend,
            "dispatch": dispatch,
            "batch": request.batch, "seq": request.seq,
            "n_levels": report.n_levels, "n_tasks": report.n_tasks,
            "verified": report.verified})
        return report

    def _execute_dataflow(self, dag, inputs, *, max_levels, verify,
                          backend, dtype_policy, kernel,
                          heterogeneity_aware, fail_ids, corrupt_ids,
                          max_workers=None):
        """Readiness-driven DAG execution (the ``execute_batch`` dataflow
        path): plans are pre-solved serially, operands pre-drawn in level
        order (the same rng stream the barrier walk consumes), then
        ``core.dataflow.run_dataflow`` dispatches nodes as their producers
        finish.  Returns level-grouped StepReports plus the
        ``price_dataflow`` overlapped prediction and the redispatch
        count."""
        from repro.core.dataflow import run_dataflow
        from repro.sim.engine import price_dataflow, price_plan

        level_groups = dag.level_order()
        if max_levels is not None:
            level_groups = level_groups[:max_levels]
        included = [i for grp in level_groups for i in grp]
        idx_of = {i: k for k, i in enumerate(included)}
        gemms = [dag.gemms[i] for i in included]
        operands = [inputs(g) for g in gemms]       # level-order rng draws
        plans, cached = [], []
        for g in gemms:
            p, c = self._solve_gemm(
                g, heterogeneity_aware=heterogeneity_aware)
            plans.append(p)
            cached.append(c)
        prices = [price_plan(g, p, self.fleet.devices)
                  for g, p in zip(gemms, plans)]
        full_deps = dag.dependencies()
        deps = [[idx_of[j] for j in full_deps[i] if j in idx_of]
                for i in included]
        overlap_time = float(price_dataflow(
            list(zip(gemms, plans)), list(self.fleet.devices), deps=deps))

        if backend == "jax" and self._pad_cache is None:
            from repro.kernels.ops import PadCache
            self._pad_cache = PadCache()
        self.fleet.table()          # build the SoA view before threading
        base_seed = int(self.rng.integers(2 ** 63 - 1))
        staged: Dict[int, tuple] = {}

        def compute(k):
            A, B = operands[k]
            return self._execute_one_deferred(
                gemms[k], plans[k], cached[k], A, B, fail_ids=fail_ids,
                corrupt_ids=corrupt_ids, verify=verify, backend=backend,
                dtype_policy=dtype_policy, kernel=kernel,
                rng=np.random.default_rng([base_seed, k]),
                staged=staged.get(k))

        def prefetch(k):
            A, B = operands[k]
            if backend == "numpy":
                staged[k] = executor.stage_operands_f64(A, B)
            elif not fail_ids:
                # warm the device-side PadCache with the node's padded
                # operands (recovery reshapes the rects, so a failing run
                # stages inside the launch instead)
                from repro.kernels import ops
                rects = [(a.r0, a.r1, a.c0, a.c1)
                         for a in plans[k].assignments]
                if rects:
                    ops.stage_plan_operands(A, B, rects,
                                            pad_cache=self._pad_cache)

        steps, dfr = run_dataflow(len(included), deps, compute,
                                  prefetch=prefetch,
                                  max_workers=max_workers)
        levels: List[LevelReport] = []
        for grp in level_groups:
            ks = [idx_of[i] for i in grp]
            lsteps = [steps[k] for k in ks]
            levels.append(LevelReport(
                steps=lsteps, backend=backend,
                level_time=float(sum(s.exec_time for s in lsteps)),
                predicted_makespan=float(max(prices[k] for k in ks)),
                verified=all(s.verified for s in lsteps),
                n_tasks=sum(s.n_tasks for s in lsteps),
                n_recovered=sum(s.n_recovered for s in lsteps)))
        return levels, overlap_time, dfr.n_redispatched

    # ---------------------------------------------------------------- train --

    def train_session(self, opt_cfg=None, *, backend: str = "numpy",
                      kernel: str = "auto", dtype_policy=None,
                      verify: bool = True, q_chunk: int = 64,
                      k_chunk: int = 64, loss_chunk: int = 64,
                      dispatch: str = "level", n_ps: int = 1,
                      diloco=None, checkpoint=None,
                      checkpoint_every: int = 100,
                      backbone_bps: Optional[float] = None):
        """A fresh PS-centric training session
        (:class:`repro.train_loop.FleetTrainSession`): every projection GEMM
        of ``session.step(params, opt_state, batch)`` — forward and the
        dA/dW backward mirrors — executes through this runtime's fleet
        executors (plan cache, Freivalds, churn recovery), while the PS
        hosts norms/softmax/loss/AdamW (§3.2).

        ``dispatch="dataflow"`` defers each GEMM's Freivalds verification
        off the critical path (overlapped with the next GEMM's compute)
        and prices the step with the barrier-free overlap model;
        ``dispatch="level"`` (default) verifies inline — the oracle the
        parity suites pin.

        ``checkpoint`` (a directory path or a
        :class:`~repro.checkpointing.checkpoint.CheckpointManager`) enables
        periodic PS-side snapshots every ``checkpoint_every`` steps;
        ``session.restore(...)`` resumes bit-exactly.

        ``n_ps > 1`` (or ``n_ps=None`` for envelope auto-sizing, or an
        explicit ``diloco`` config) instead returns a
        :class:`repro.train_loop.MultiPSTrainSession`: the fleet is
        partitioned into flops-balanced PS islands (``api.ShardedFleet``),
        each island runs H local inner steps per round
        (``diloco.inner_steps``), and the sharded DiLoCo outer loop syncs
        them at round boundaries — ``n_ps=1`` with ``inner_steps=1`` is
        bit-identical to the single-PS session.  ``backbone_bps``
        optionally prices the cross-PS sync over one shared backbone link
        instead of per-PS NICs."""
        if n_ps is None or n_ps > 1 or diloco is not None:
            from repro.train_loop import MultiPSTrainSession
            return MultiPSTrainSession(
                self, n_ps=n_ps, opt_cfg=opt_cfg, diloco=diloco,
                backend=backend, kernel=kernel, dtype_policy=dtype_policy,
                verify=verify, q_chunk=q_chunk, k_chunk=k_chunk,
                loss_chunk=loss_chunk, dispatch=dispatch,
                checkpoint=checkpoint, checkpoint_every=checkpoint_every,
                backbone_bps=backbone_bps)
        from repro.train_loop import FleetTrainSession
        return FleetTrainSession(self, opt_cfg=opt_cfg, backend=backend,
                                 kernel=kernel, dtype_policy=dtype_policy,
                                 verify=verify, q_chunk=q_chunk,
                                 k_chunk=k_chunk, loss_chunk=loss_chunk,
                                 dispatch=dispatch, checkpoint=checkpoint,
                                 checkpoint_every=checkpoint_every)

    def train_step(self, params, opt_state, batch, *, opt_cfg=None,
                   backend: str = "numpy", kernel: str = "auto",
                   verify: bool = True,
                   fail_ids: Sequence[int] = (), fail_at_gemm: int = 0,
                   q_chunk: int = 64, k_chunk: int = 64,
                   loss_chunk: int = 64, dispatch: str = "level"):
        """One fleet-executed training step of the session architecture:
        numerically matches the monolithic jitted
        ``launch.steps.make_train_step`` while every DAG GEMM runs on the
        fleet.  Returns ``(params, opt_state, metrics)``;
        ``metrics["fleet"]`` is the per-step
        :class:`~repro.train_loop.FleetStepReport` (measured executor time
        vs ``engine.price_plan`` predicted makespan, task/recovery counts,
        cache hit rate).

        ``fail_ids`` injects a mid-step device failure at the
        ``fail_at_gemm``-th GEMM — the in-flight GEMM recovers exactly via
        ``churn.recover``, the devices are evicted, and cached plans are
        patched — without corrupting the step.  Sessions are cached per
        option set, so repeated calls stay warm; use :meth:`train_session`
        for explicit session control."""
        # AdamConfig is a frozen dataclass: keying by value means equal
        # configs share a warm session (and a dead config's recycled id
        # can never resurrect the wrong optimizer settings); normalize
        # None to the default so it shares too
        if opt_cfg is None:
            from repro.optim import adam
            opt_cfg = adam.AdamConfig()
        key = (opt_cfg, backend, kernel, verify, q_chunk, k_chunk,
               loss_chunk, dispatch)
        session = self._train_sessions.get(key)
        if session is None:
            session = self.train_session(
                opt_cfg, backend=backend, kernel=kernel, verify=verify,
                q_chunk=q_chunk, k_chunk=k_chunk, loss_chunk=loss_chunk,
                dispatch=dispatch)
            self._train_sessions[key] = session
        return session.step(params, opt_state, batch, fail_ids=fail_ids,
                            fail_at_gemm=fail_at_gemm)

    # ---------------------------------------------------------------- serve --

    def serve_session(self, params=None, *, slots: int = 8,
                      page_size: int = 16, max_len: int = 64,
                      kv_int8: bool = False, backend: str = "numpy",
                      kernel: str = "auto", dtype_policy=None,
                      verify: bool = True, check_paged_read: bool = False,
                      n_pages: Optional[int] = None, seed: int = 0,
                      dispatch: str = "level"):
        """A fleet-backed decode serving session
        (:class:`repro.serving.ServeSession`): continuous batching over
        ``slots`` fixed batch lanes, prompt/generation K/V in a PS-hosted
        paged cache (``page_size``-token pages, reserved per request at
        admission, ``kv_int8`` for int8 + f16-scale storage), and every
        per-token projection GEMM — attn q/k/v/out or MLA latent
        projections, SwiGLU, lm_head — coalesced across the batch and
        executed on this runtime's fleet (plan cache, Freivalds, churn
        recovery).  ``submit()`` requests, ``step()``/``run()`` to decode;
        the report prices every step with ``sim/engine`` next to measured
        wall time (docs/SERVING.md).  ``dispatch="dataflow"`` defers each
        GEMM's verification off the decode critical path and prices the
        step's GEMM chain through ``engine.price_dataflow`` (handoff
        overlap) instead of the per-GEMM barrier sum."""
        from repro.serving import ServeSession
        return ServeSession(self, params, slots=slots, page_size=page_size,
                            max_len=max_len, kv_int8=kv_int8,
                            backend=backend, kernel=kernel,
                            dtype_policy=dtype_policy, verify=verify,
                            check_paged_read=check_paged_read,
                            n_pages=n_pages, seed=seed, dispatch=dispatch)

    # -------------------------------------------------------------- recover --

    def on_failure(self, ids: Sequence[int]) -> ChurnReport:
        """Evict failed devices from the session fleet and incrementally
        patch every cached plan: survivors keep their shards, only the
        orphaned rectangles are re-solved (cache-aware, §4.2).  Patched
        plans land in the *new* fleet signature's cache, so the next
        :meth:`plan` / :meth:`execute_step` is warm instead of cold."""
        failed = set(int(i) for i in ids)
        new_fleet = self.fleet.without(failed)
        if not len(new_fleet):
            raise RuntimeError("no surviving devices")
        survivors = new_fleet.table()   # one SoA view for every patch solve
        old_sig, new_sig = self.fleet.signature(), new_fleet.signature()
        t0 = time.perf_counter()
        patched = carried = dropped = 0
        worst_time = worst_frac = 0.0
        for het in (True, False):
            old_cache = self._plan_caches.get((old_sig, het), {})
            if not old_cache:
                continue
            new_cache = self._plan_caches.setdefault((new_sig, het), {})
            for key, plan in old_cache.items():
                if key in new_cache:
                    continue
                out = _patch_plan(plan, failed, survivors)
                if out is None:
                    dropped += 1
                    continue
                new_plan, rec = out
                new_cache[key] = new_plan
                if rec is None:
                    carried += 1
                else:
                    patched += 1
                    worst_time = max(worst_time, rec.recovery_time)
                    worst_frac = max(worst_frac, rec.recomputed_fraction)
        report = ChurnReport(
            failed_ids=sorted(failed), n_survivors=len(new_fleet),
            n_plans_patched=patched, n_plans_carried=carried,
            n_plans_dropped=dropped,
            recovery_time=worst_time, recomputed_fraction=worst_frac,
            solve_time=time.perf_counter() - t0,
            fleet_signature=new_sig)
        self.fleet = new_fleet
        self.history.append({
            "event": "on_failure", "failed_ids": report.failed_ids,
            "n_survivors": report.n_survivors,
            "n_plans_patched": report.n_plans_patched,
            "n_plans_carried": report.n_plans_carried,
            "n_plans_dropped": report.n_plans_dropped})
        return report

    def on_join(self, device: cm.Device, keep_id: bool = False) -> Fleet:
        """Admit a joiner: folded into the fleet for the next round (§3.2).
        The fleet signature changes, so subsequent plans re-solve and start
        assigning the newcomer work.  ``keep_id=True`` preserves the
        joiner's device id (island reassignment after a PS failure — the
        device already has a fleet-wide identity)."""
        self.fleet = self.fleet.admit(device, keep_id=keep_id)
        return self.fleet

    # -------------------------------------------------------------- stream --

    def stream_profile(self, gemm: cm.GEMM, *, alpha: int = 10,
                       beta: int = 10, k: int = 64,
                       pareto_alpha: float = 0.0,
                       device: Optional[cm.Device] = None,
                       n_trials: int = 20) -> StreamReport:
        """Profile the streamed row-column pipeline (Eq. 9') for ``k``
        (alpha x beta) work quanta on a representative device, with optional
        Pareto(α) stage jitter, and apply the session mitigation policy to
        the jittered latency.

        ``pareto_alpha=0`` (the default) means a deterministic profile; any
        other value must exceed 1 for a finite-mean Pareto, matching the
        ``tail``/``streaming`` entry points (a value in (0, 1] used to be
        silently treated as "no jitter")."""
        from repro.core import streaming, tail
        if pareto_alpha != 0.0:
            tail.require_alpha_gt1(pareto_alpha, "stream_profile")
        if device is None:
            devs = sorted(self.fleet.devices, key=lambda d: d.flops)
            device = devs[len(devs) // 2]
        c = streaming.pair_cost(gemm, device, alpha=alpha, beta=beta)
        serial = k * (device.dl_lat + c.t_dl + c.t_comp + c.t_ul
                      + device.ul_lat)
        piped = streaming.pipeline_time(c, k, dl_lat=device.dl_lat,
                                        ul_lat=device.ul_lat)
        if pareto_alpha > 1.0:
            jittered = float(np.mean([
                streaming.simulate_stream(c, k, device.dl_lat,
                                          device.ul_lat, jitter=self.rng,
                                          pareto_alpha=pareto_alpha)
                for _ in range(n_trials)]))
        else:
            jittered = piped
        report = StreamReport(serial_time=serial, pipelined_time=piped,
                              jittered_time=jittered,
                              mitigation=self.mitigation.mitigate(jittered))
        self.history.append({
            "event": "stream_profile", "k": k,
            "overlap_speedup": report.overlap_speedup})
        return report

    # ------------------------------------------------------------ simulate --

    def simulate(self, batch: Optional[int] = None,
                 seq: Optional[int] = None, *,
                 request: Optional[PlanRequest] = None,
                 events: Sequence[TimelineEvent] = (),
                 backend: str = "event",
                 jitter_alpha: float = 0.0,
                 ps_contention: bool = False,
                 seed: Optional[int] = None,
                 trace: bool = False) -> TimelineReport:
        """Price one batch on a simulation backend.

        ``backend="analytic"`` returns the closed-form accounting
        (Eq. 1/2-5) as a :class:`TimelineReport` — the fast path, but it
        cannot price events.  ``backend="event"`` replays the solved
        schedule on the discrete-event fleet engine: ``events`` (built with
        :mod:`repro.sim.events` ``fail``/``join``/``slowdown``) are injected
        on the timeline, ``jitter_alpha`` adds per-stage Pareto(α) jitter,
        and ``ps_contention=True`` bounds aggregate transfers by the session
        ``PSConfig.net_bw`` (§6 envelope).  With no events, no jitter, and
        no contention the event backend reproduces the analytic unicast
        batch time exactly (tested to 1e-6 relative).
        ``backend="event-array"`` prices the identical scenario on the
        struct-of-arrays engine (:mod:`repro.sim.engine_array`) — same
        TimelineReport to <=1e-9, vectorized hot loop for 10k–1M-device
        fleets; scenarios outside its bit-exact envelope (jitter, proven
        PS queueing) transparently replay on the scalar oracle.

        Simulation never mutates the session: a ``fail`` event here prices
        the what-if; call :meth:`on_failure` to actually evict devices."""
        if request is None:
            if batch is None or seq is None:
                raise ValueError("simulate() needs batch+seq or a "
                                 "PlanRequest")
            request = PlanRequest(
                batch=batch, seq=seq,
                attention_scores=self.attention_scores,
                heterogeneity_aware=self.heterogeneity_aware)
        from repro.sim import engine as eng_mod
        from repro.sim.events import validate_events
        evs = validate_events(list(events))
        if backend == "analytic":
            if evs or jitter_alpha or ps_contention:
                raise ValueError(
                    "backend='analytic' cannot price injected events, "
                    "jitter, or PS contention; use backend='event'")
            sp = self.plan(request=request).schedule
            report = TimelineReport(
                backend="analytic", makespan=sp.batch_time,
                gemm_time=sp.gemm_time, opt_tail=sp.opt_tail,
                level_times=list(sp.level_times))
        elif backend in ("event", "event-array"):
            from repro.sim.events import FailEvent, SlowdownEvent
            known = {d.device_id for d in self.fleet.devices}
            known |= {e.device.device_id for e in evs
                      if not isinstance(e, (FailEvent, SlowdownEvent))}
            for e in evs:
                if isinstance(e, (FailEvent, SlowdownEvent)) \
                        and e.device_id not in known:
                    raise ValueError(
                        f"{e!r} targets device {e.device_id}, which is "
                        f"neither in the session fleet nor joined by an "
                        f"earlier event")
            sp = self.plan(request=request).schedule
            cap = self.ps.net_bw if ps_contention else None
            rng = np.random.default_rng(self.seed if seed is None else seed)
            engine_cls = None
            if backend == "event-array":
                from repro.sim.engine_array import ArrayTimelineEngine
                engine_cls = ArrayTimelineEngine
            report = eng_mod.simulate_schedule(
                sp, events=evs, ps_egress_bps=cap, ps_ingress_bps=cap,
                jitter_alpha=jitter_alpha, rng=rng,
                heterogeneity_aware=request.heterogeneity_aware,
                trace=trace, engine_cls=engine_cls)
        else:
            raise ValueError(f"unknown backend {backend!r}; expected "
                             "'analytic', 'event', or 'event-array'")
        self.history.append({
            "event": "simulate", "backend": backend,
            "batch": request.batch, "seq": request.seq,
            "n_events": report.n_events, "makespan": report.makespan,
            "n_failures": report.n_failures, "n_joins": report.n_joins})
        return report

    # ----------------------------------------------------------- internals --

    def _dag(self, request: PlanRequest) -> GemmDag:
        key = request
        if key not in self._dag_cache:
            self._dag_cache[key] = build_dag(
                self.cfg, request.batch, request.seq,
                backward=request.backward, lm_head=request.lm_head,
                attention_scores=request.attention_scores)
        return self._dag_cache[key]

    def _cache(self, heterogeneity_aware: bool) -> Dict[tuple, cm.Plan]:
        return self._plan_caches.setdefault(
            (self.fleet.signature(), heterogeneity_aware), {})

    def _solve_gemm(self, gemm: cm.GEMM,
                    heterogeneity_aware: Optional[bool] = None
                    ) -> Tuple[cm.Plan, bool]:
        het = self.heterogeneity_aware if heterogeneity_aware is None \
            else heterogeneity_aware
        cache = self._cache(het)
        key = plan_shape_key(gemm) + (gemm.count,)
        if key in cache:
            return cache[key], True
        # same solver path as schedule() — including the session's
        # heterogeneity setting — so cache entries are identical regardless
        # of whether plan(), plan_gemm(), or execute_step() created them
        if het:
            plan = solve_level_gemm(gemm, self.fleet.table())
        else:
            plan = solve_level_gemm(gemm, self.fleet.homogenized_table())
            reprice_plan(plan, self.fleet.table())
        cache[key] = plan
        return plan, False


# ------------------------------------------------------------ plan patching --

def _patch_plan(plan: cm.Plan, failed: set,
                survivors: cm.Fleetlike
                ) -> Optional[Tuple[cm.Plan, Optional[churn.RecoveryResult]]]:
    """Carry one cached plan across a churn event: survivors keep their
    rectangles; each orphaned rectangle is re-solved over the survivors with
    cache-aware communication and grafted back in place.  Returns ``None``
    when the plan cannot be patched (instance-granular or n-split plans
    re-solve cold instead)."""
    if plan.instances is not None or plan.n_split != 1:
        return None
    orphans = [a for a in plan.assignments if a.device_id in failed]
    if not orphans:
        # untouched by this failure; reuse under the new signature
        return plan, None
    table = cm.DeviceTable.ensure(survivors)
    hit = sorted(failed & {a.device_id for a in plan.assignments})
    event = churn.FailureEvent(gemm=plan.gemm, failed_ids=hit, plan=plan)
    rec = churn.recover(event, table)
    assignments = [a for a in plan.assignments if a.device_id not in failed]
    # iterate the (rect, patch) pairs — recover() may skip degenerate
    # orphans, so zipping against `orphans` could misalign patch offsets
    for rect, patch in rec.patches:
        for pa in patch.assignments:
            assignments.append(cm.Assignment(
                device_id=pa.device_id,
                r0=rect.r0 + pa.r0, r1=rect.r0 + pa.r1,
                c0=rect.c0 + pa.c0, c1=rect.c0 + pa.c1))
    active = {a.device_id for a in assignments}
    new_plan = cm.Plan(
        gemm=plan.gemm, assignments=assignments, makespan=0.0,
        lower_bound=cm.lower_bound(plan.gemm, table),
        excluded=[int(i) for i in table.ids if int(i) not in active])
    new_plan.makespan = cm.plan_makespan(plan.gemm, table, new_plan)
    return new_plan, rec
