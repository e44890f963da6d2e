"""Differentiable fleet GEMM: the bridge between JAX autodiff on the PS and
the CLEAVE executors on the (simulated) device fleet.

``fleet_dot(a, b)`` is a ``jax.custom_vjp`` primitive whose primal *and*
both cotangents are executed by the session runtime's fleet executor:

* forward:   C  = A·B          (the traced forward GEMM, §3.2)
* backward:  dA = dO·Bᵀ        (same shapes transposed — ``gemm_dag``'s
  ``.dA`` mirror)
*            dW = Aᵀ·dO        (the weight gradient — ``.dW`` mirror)

Each host call goes through :meth:`CleaveRuntime.execute_step`, i.e. the
plan cache, the failure/recovery path (``churn.recover``), Freivalds
verification, and — for ``backend="jax"`` — the Pallas/XLA batched kernels.
The jax backend takes the operands as the device arrays they are: they are
padded on the device and never copied to the host, save the slices of
blocks that verification flags.  The numpy backend copies both to the host
first.

Sessions are process-global and non-nested (the one ``custom_vjp``
primitive is shared by every caller and cannot thread ``self``), opened via
:meth:`FleetGemmSession.open`, which also installs the ``models.layers.pdot``
hook.  The fleet step must run **eagerly** (no outer ``jax.jit``): under
eager autodiff the primal and both cotangent rules see concrete arrays, so
the host executes each GEMM directly, between device programs.  The
model's unrolled path (``forward(..., scan_layers=False)``) keeps the GEMMs
out of compiled scans.  A host callback would not do: JAX runs a
``pure_callback`` body with the CPU as default device, so a jax executor
inside it would stage its operands off the accelerator.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.spans import span
from repro.train_loop import hook as _hook

_SESSION: Optional["FleetGemmSession"] = None


@dataclass
class GemmRecord:
    """One fleet-executed GEMM inside a training step."""
    m: int
    n: int
    q: int
    kind: str                   # 'fwd' | 'dA' | 'dW'
    exec_time: float            # host wall-clock of the fleet execution
    #                             (dataflow dispatch: the compute phase
    #                             only — verification overlaps downstream)
    predicted_makespan: float   # engine.price_plan of the executed plan
    n_tasks: int
    n_recovered: int
    verified: bool
    plan_cached: bool
    failed_ids: Tuple[int, ...] = ()
    b: int = 4                  # element width the plan was solved for
    verify_time: float = 0.0    # wall of the Freivalds check: inside
    #                             exec_time under level dispatch, off the
    #                             critical path under dataflow
    # host seconds per span of the round trip, by short name: d2h, plan,
    # tasks, stage, kernel, fetch, scatter, verify, h2d (jax backend;
    # the numpy executor has plan, tasks, verify)
    phases: Dict[str, float] = field(default_factory=dict)
    roundtrip_time: float = 0.0  # the ``cleave.fleet.gemm`` span: operands
    #                              on the device to the output back there
    padded_flops: float = 0.0   # GEMM FLOPs launched, padding included
    #                             (jax backend; 0 on numpy)
    host_operand_bytes: int = 0  # operand bytes copied to the host: A + B
    #                              on the numpy backend, 0 on the jax
    #                              backend save flagged blocks' slices

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.n * self.q


def sum_phases(records: Sequence[GemmRecord]) -> Dict[str, float]:
    """The records' phases summed by name, with their round trips under
    ``gemm``."""
    out: Dict[str, float] = {}
    for r in records:
        for k, v in r.phases.items():
            out[k] = out.get(k, 0.0) + v
    if records:
        out["gemm"] = sum(r.roundtrip_time for r in records)
    return out


def phases_line(phases: Dict[str, float], host_operand_bytes: int) -> str:
    """`` | host operands <MB> MB | spans <name> <seconds>s ...`` for a
    step report's log line."""
    if not phases:
        return ""
    return (f" | host operands {host_operand_bytes / 1e6:.1f} MB"
            " | spans " + " ".join(f"{k} {v:.3f}s"
                                   for k, v in phases.items()))


@dataclass
class _ArmedFailure:
    """A scheduled mid-step device failure: injected into the ``at_gemm``-th
    fleet execution of the step, then (optionally) escalated to a permanent
    departure via ``CleaveRuntime.on_failure``."""
    fail_ids: Tuple[int, ...]
    at_gemm: int
    evict: bool = True
    fired: bool = False


class FleetGemmSession:
    """Owns the per-step GEMM trace and the executor options for one
    PS-centric training run.  Reused across steps so plan caches stay warm
    and per-step records can be harvested via :meth:`drain`."""

    def __init__(self, runtime, *, backend: str = "numpy",
                 kernel: str = "auto", dtype_policy=None,
                 verify: bool = True, dispatch: str = "level"):
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown fleet backend {backend!r}; "
                             "expected 'numpy' or 'jax'")
        if dispatch not in ("level", "dataflow"):
            raise ValueError(f"unknown dispatch {dispatch!r}; "
                             "expected 'level' or 'dataflow'")
        self.rt = runtime
        self.backend = backend
        self.kernel = kernel
        self.dtype_policy = dtype_policy
        self.verify = verify
        # 'dataflow': each GEMM's Freivalds verification is deferred onto a
        # background worker, overlapping the next GEMM's compute (autodiff
        # serializes the GEMMs themselves — the verify is the one step-loop
        # stage that can legally leave the critical path).  drain() joins
        # the outstanding checks and back-fills the records, so a step's
        # verified flag is always final by the time its report exists.
        self.dispatch = dispatch
        self.records: List[GemmRecord] = []
        self.churn_reports: list = []
        self._armed: Optional[_ArmedFailure] = None
        self._gemm_index = 0
        self._verify_pool = None
        self._pending: List[tuple] = []     # (record, StepReport, future)
        # (m, n, q, fleet signature) -> price_plan, so steady-state steps
        # don't re-walk identical plans just to stamp their records
        self._price_memo: dict = {}
        # (shape trace, fleet signature) -> price_dataflow makespan of a
        # step's GEMM chain (price_step); decode steps repeat identical
        # traces, so this hits after the first step
        self._trace_price_memo: dict = {}

    # ------------------------------------------------------------- control --

    @contextlib.contextmanager
    def open(self):
        """Make this session the process-global GEMM executor and install
        the ``pdot`` hook for the extent of the block."""
        global _SESSION
        if _SESSION is not None:
            raise RuntimeError("a FleetGemmSession is already open")
        _SESSION = self
        try:
            with _hook.use_hook(self.dot):
                yield self
        finally:
            _SESSION = None

    def arm_failure(self, fail_ids: Sequence[int], *, at_gemm: int = 0,
                    evict: bool = True) -> None:
        """Schedule ``fail_ids`` to vanish during the ``at_gemm``-th fleet
        GEMM of the upcoming step: the in-flight GEMM recovers through
        ``churn.recover`` (numerically exact), and with ``evict=True`` the
        devices are then permanently removed (``CleaveRuntime.on_failure``),
        so every later GEMM plans over the survivors."""
        ids = tuple(int(i) for i in fail_ids)
        known = set(self.rt.fleet.ids())
        missing = [i for i in ids if i not in known]
        if missing:
            raise ValueError(f"cannot fail unknown devices {missing}")
        self._armed = _ArmedFailure(fail_ids=ids, at_gemm=int(at_gemm),
                                    evict=evict)

    def drain(self) -> Tuple[List[GemmRecord], list]:
        """Harvest (and clear) the per-step state accumulated since the
        last call: the GEMM trace and any churn reports this step's
        failures produced.  Joins any deferred verifications first
        (dataflow dispatch) and back-fills their records, so the harvested
        trace always carries final ``verified`` flags.  Also disarms a
        pending failure, so an aborted step can't leak its injection into
        the next one."""
        for record, step, fut in self._pending:
            record.verify_time = record.phases["verify"] = fut.result()
            record.verified = step.verified
            record.n_recovered = step.n_recovered
            record.host_operand_bytes = step.host_operand_bytes
        self._pending = []
        out, self.records = self.records, []
        churn, self.churn_reports = self.churn_reports, []
        self._gemm_index = 0
        self._armed = None
        return out, churn

    # ------------------------------------------------------------ GEMM ops --

    def dot(self, x, w):
        """The ``pdot`` hook: ``x @ w`` with leading dims flattened to the
        GEMM's ``m`` — differentiable, with both cotangent GEMMs also
        fleet-executed."""
        lead = x.shape[:-1]
        out = _fleet_dot(x.reshape(-1, x.shape[-1]), w)
        return out.reshape(lead + (w.shape[-1],))

    def _price(self, gemm, plan) -> float:
        from repro.sim.engine import price_plan
        key = (gemm.m, gemm.n, gemm.q, gemm.b,
               self.rt.fleet.signature())
        if key not in self._price_memo:
            self._price_memo[key] = price_plan(gemm, plan,
                                               self.rt.fleet.devices)
        return self._price_memo[key]

    def price_step(self, records: Sequence[GemmRecord]) -> float:
        """Engine price of one step's executed GEMM trace, matching the
        session dispatch.  Level: each GEMM is a full PS round trip, so the
        step costs the barrier sum of per-plan makespans.  Dataflow: the
        trace is priced as a dependency *chain* through
        ``engine.price_dataflow`` — GEMM k+1's operand downloads stream
        behind GEMM k's uploads (§3.2 overlap), which is what the virtual
        serve clock should charge when verification and staging are off
        the critical path.  Memoized per (shape trace, fleet signature):
        decode steps at fixed slot count repeat the identical trace."""
        if self.dispatch != "dataflow":
            return float(sum(r.predicted_makespan for r in records))
        if not records:
            return 0.0
        key = (tuple((r.m, r.n, r.q, r.b) for r in records),
               self.rt.fleet.signature())
        hit = self._trace_price_memo.get(key)
        if hit is None:
            from repro.core import cost_model as cm
            from repro.sim.engine import price_dataflow
            nodes = []
            for r in records:
                g = cm.GEMM(m=r.m, n=r.n, q=r.q, b=r.b)
                plan, _ = self.rt._solve_gemm(g)
                nodes.append((g, plan))
            deps = [[] if i == 0 else [i - 1] for i in range(len(nodes))]
            hit = float(price_dataflow(nodes, list(self.rt.fleet.devices),
                                       deps=deps))
            self._trace_price_memo[key] = hit
        return hit

    def _execute(self, a, b, kind: str, phases: Dict[str, float]
                 ) -> Tuple[np.ndarray, GemmRecord]:
        """Run one GEMM on the fleet; returns its output and its record,
        whose ``phases`` is ``phases`` with the runtime's spans added."""
        fail_ids: Tuple[int, ...] = ()
        armed = self._armed
        if armed is not None and not armed.fired \
                and self._gemm_index >= armed.at_gemm:
            fail_ids = armed.fail_ids
            armed.fired = True
        self._gemm_index += 1

        from repro.core import cost_model as cm
        # carry the real element width so the plan (and its cache key)
        # matches what the DAG pricing solved for the same shape — a f32
        # training GEMM is b=4, not the cm.GEMM default of 2
        gemm = cm.GEMM(m=a.shape[0], n=a.shape[1], q=b.shape[1],
                       b=int(a.dtype.itemsize))
        if self.dispatch == "dataflow":
            rep, fin = self.rt.execute_step_deferred(
                a, b, gemm=gemm, fail_ids=fail_ids, verify=self.verify,
                backend=self.backend, dtype_policy=self.dtype_policy,
                kernel=self.kernel)

            def _timed_verify():
                t0 = time.perf_counter()
                fin()
                return time.perf_counter() - t0

            if self._verify_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._verify_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="fleet-verify")
            self._pending.append(
                (None, rep, self._verify_pool.submit(_timed_verify)))
        else:
            rep = self.rt.execute_step(
                a, b, gemm=gemm, fail_ids=fail_ids, verify=self.verify,
                backend=self.backend, dtype_policy=self.dtype_policy,
                kernel=self.kernel)
        for k, v in rep.phases.items():
            phases[k] = phases.get(k, 0.0) + v
        with span("cleave.fleet.plan", phases):
            predicted = self._price(rep.gemm, rep.plan)
        record = GemmRecord(
            m=rep.gemm.m, n=rep.gemm.n, q=rep.gemm.q, kind=kind,
            exec_time=rep.exec_time, predicted_makespan=predicted,
            n_tasks=rep.n_tasks, n_recovered=rep.n_recovered,
            verified=rep.verified, plan_cached=rep.plan_cached,
            failed_ids=fail_ids, b=gemm.b,
            verify_time=phases.get("verify", 0.0), phases=phases,
            padded_flops=rep.padded_flops,
            host_operand_bytes=rep.host_operand_bytes)
        if self.dispatch == "dataflow":
            # back-patch the record once its deferred check lands (drain)
            self._pending[-1] = (record, rep, self._pending[-1][2])
        self.records.append(record)
        if fail_ids and armed is not None and armed.evict:
            # the failed devices are gone for good: evict them and patch the
            # plan cache so the rest of the step plans over survivors
            self.churn_reports.append(self.rt.on_failure(fail_ids))
        return rep.output, record


# ------------------------------------------------------- custom-vjp fleet dot

def _host_gemm(kind: str, a, b) -> Tuple[np.ndarray, GemmRecord]:
    sess = _SESSION
    if sess is None:
        raise RuntimeError("fleet GEMM outside an open FleetGemmSession: "
                           "open one with FleetGemmSession.open()")
    phases: Dict[str, float] = {}
    # the numpy backend's copy waits on whatever device op produced the
    # operands; the jax backend keeps them on the device (its kernel
    # phase waits on their producers), so its span here is empty
    with span("cleave.fleet.d2h", phases):
        if sess.backend != "jax":
            a, b = np.asarray(a), np.asarray(b)
    return sess._execute(a, b, kind, phases)


def _raw_fleet_dot(a, b, kind: str):
    import jax
    import jax.numpy as jnp

    if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
        raise TypeError(
            "fleet GEMMs execute on concrete operands: run the fleet step "
            "eagerly, with no jax.jit, vmap or scan around it")
    roundtrip: Dict[str, float] = {}
    with span("cleave.fleet.gemm", roundtrip):
        out, record = _host_gemm(kind, a, b)
        # the upload is enqueued, not waited on: the span is its host side
        with span("cleave.fleet.h2d", record.phases):
            out = jnp.asarray(np.ascontiguousarray(out).astype(
                a.dtype, copy=False))
    record.roundtrip_time = roundtrip["gemm"]
    return out


def _make_fleet_dot():
    import jax

    @jax.custom_vjp
    def fleet_dot(a, b):
        return _raw_fleet_dot(a, b, "fwd")

    def _fwd(a, b):
        return _raw_fleet_dot(a, b, "fwd"), (a, b)

    def _bwd(res, g):
        a, b = res
        da = _raw_fleet_dot(g, b.T, "dA")       # dA = dO · Bᵀ
        dw = _raw_fleet_dot(a.T, g, "dW")       # dW = Aᵀ · dO
        return da, dw

    fleet_dot.defvjp(_fwd, _bwd)
    return fleet_dot


_FLEET_DOT = None


def _fleet_dot(a, b):
    global _FLEET_DOT
    if _FLEET_DOT is None:
        _FLEET_DOT = _make_fleet_dot()
    return _FLEET_DOT(a, b)
