"""Fleet-backed decode serving session: continuous batching over a paged
KV cache, with every projection GEMM executed on the device fleet.

One :class:`ServeSession` owns the PS-side state — model params, the
:class:`~repro.serving.kv_cache.PagedKVCache`, the
:class:`~repro.serving.batcher.ContinuousBatcher` — and a
:class:`~repro.train_loop.fleet_gemm.FleetGemmSession` bound to the
:class:`~repro.api.CleaveRuntime` whose fleet executes the GEMMs.

Each :meth:`step` decodes **one token for every occupied batch slot**:

* admission: arrived requests take free slots, reserve their full page
  budget, and prefill their prompt (minus the last token) monolithically on
  the PS — the prompt K/V lands in pages, and the request's first decode
  step feeds ``prompt[-1]``, so the float and int8 paths are both
  token-identical to the monolithic driver;
* the pools gather to contiguous (L, B, Smax, ...) views (the PS reading
  its own pages), and ``models.model.decode_step`` runs **eagerly** with the
  layer loop unrolled and the ``pdot`` hook open — the batch's q/k/v/out
  (or MLA latent) projections, SwiGLU, and lm_head each coalesce into one
  fleet-executed (B_slots, ·)·(·, ·) GEMM.  Slot count is fixed, so every
  step re-executes the same GEMM shapes: after the first step the plan
  cache is warm for the life of the session;
* greedy sampling, new-token K/V scattered back into pages, retirement.

The session keeps two clocks: measured wall time, and a **virtual clock**
advanced each step by the summed ``sim/engine.price_plan`` makespan of the
step's executed plans — what the modeled edge fleet would have taken.  Both
feed the latency report (:meth:`report`).

A device failure injected mid-step (``step(fail_ids=...)``) recovers
in-flight through ``churn.recover`` — the GEMM output is exact, so no
request's KV state is corrupted — and then evicts the device, patching
cached plans so later steps plan over the survivors.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.spans import span
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.kv_cache import PagedKVCache
from repro.train_loop.fleet_gemm import (FleetGemmSession, GemmRecord,
                                         phases_line, sum_phases)


@dataclass
class ServeStepReport:
    """One continuous-batching decode step."""
    step: int
    n_active: int
    n_admitted: int
    n_retired: int
    wall_time: float             # measured host wall (prefill + decode)
    priced_makespan: float       # engine.price_plan sum over the step's GEMMs
    n_gemms: int
    n_tasks: int
    n_recovered: int
    verified: bool
    plan_cache_hit_rate: float
    failed_ids: Tuple[int, ...] = ()
    records: List[GemmRecord] = field(default_factory=list, repr=False)
    # host seconds per span: the step's own (step, admit, gather,
    # kv_upload, decode, sample, kv_write) and its fleet GEMMs' phases
    # summed (``fleet_gemm.sum_phases``)
    phases: Dict[str, float] = field(default_factory=dict)
    host_operand_bytes: int = 0  # summed GemmRecord.host_operand_bytes


@dataclass
class ServeReport:
    """Aggregate latency report over the finished requests of a session."""
    n_requests: int
    n_tokens: int
    n_steps: int
    wall_time: float             # total measured step wall
    virtual_time: float          # total engine-priced fleet time
    tokens_per_sec: float        # measured
    tokens_per_sec_priced: float
    token_lat_p50: float         # measured per-token latency
    token_lat_p99: float
    token_lat_p50_priced: float
    token_lat_p99_priced: float
    e2e_p50: float               # measured request latency (arrival→finish)
    e2e_p99: float
    e2e_p50_priced: float
    e2e_p99_priced: float
    plan_cache_hit_rate: float
    n_recovered: int
    failed_ids: Tuple[int, ...] = ()
    cache: Optional[object] = None        # kv_cache.CacheStats
    phases: Dict[str, float] = field(default_factory=dict)  # over steps
    host_operand_bytes: int = 0  # over steps

    def log_line(self) -> str:
        s = (f"serve: {self.n_requests} reqs {self.n_tokens} toks in "
             f"{self.n_steps} steps | {self.tokens_per_sec:.1f} tok/s "
             f"measured ({self.tokens_per_sec_priced:.1f} priced) | "
             f"token p50/p99 {self.token_lat_p50 * 1e3:.1f}/"
             f"{self.token_lat_p99 * 1e3:.1f} ms | "
             f"cache {self.plan_cache_hit_rate:.0%}")
        if self.failed_ids:
            s += (f" | failed {list(self.failed_ids)} recovered "
                  f"{self.n_recovered} tasks")
        return s + phases_line(self.phases, self.host_operand_bytes)


class ServeSession:
    """Continuous-batching fleet decode (module docstring).

    Built via :meth:`repro.api.CleaveRuntime.serve_session`.  ``slots`` is
    the fixed decode batch width; ``max_len`` caps any request's
    prompt + max_new budget; the page pool defaults to exactly enough pages
    to fill every slot (``n_pages`` overrides)."""

    def __init__(self, runtime, params=None, *, cfg=None, slots: int = 8,
                 page_size: int = 16, max_len: int = 64,
                 kv_int8: bool = False, backend: str = "numpy",
                 kernel: str = "auto", dtype_policy=None,
                 verify: bool = True, check_paged_read: bool = False,
                 n_pages: Optional[int] = None, seed: int = 0,
                 dispatch: str = "level"):
        import jax

        from repro.models import model as M
        self.rt = runtime
        self.cfg = cfg if cfg is not None else runtime.cfg
        if params is None:
            params = M.init_params(self.cfg, jax.random.PRNGKey(seed))
        self.params = params
        self.slots = int(slots)
        self.page = int(page_size)
        self.cache_len = self.page * math.ceil(max_len / self.page)
        pages_per_req = self.cache_len // self.page
        self.kv = PagedKVCache(
            self.cfg, page_size=self.page, kv_int8=kv_int8,
            n_pages=(n_pages if n_pages is not None
                     else self.slots * pages_per_req))
        self.batcher = ContinuousBatcher(self.slots, self.kv)
        # dispatch="dataflow": deferred (overlapped) Freivalds checks, and
        # the virtual clock charges each step its GEMM chain's
        # price_dataflow critical path instead of the barrier sum
        self.dispatch = dispatch
        self.gemms = FleetGemmSession(runtime, backend=backend,
                                      kernel=kernel,
                                      dtype_policy=dtype_policy,
                                      verify=verify, dispatch=dispatch)
        self.kv_int8 = bool(kv_int8)
        self.check_paged_read = bool(check_paged_read)
        self.paged_read_checks = 0
        self.clock = 0.0           # virtual (engine-priced) time
        self.wall = 0.0            # accumulated measured step wall
        self.step_index = 0
        self.step_reports: List[ServeStepReport] = []
        self._prefill_fns: Dict[int, object] = {}
        self._check_q = None

    # -------------------------------------------------------------- intake --

    def submit(self, prompt, max_new: int, arrival: float = 0.0) -> Request:
        """Queue one request (prompt token ids + generation budget);
        admission happens between decode steps as slots and pages free."""
        req = self.batcher.submit(prompt, max_new, arrival=arrival)
        if req.budget > self.cache_len:
            raise ValueError(
                f"request budget {req.budget} exceeds the session max_len "
                f"capacity {self.cache_len}")
        return req

    def _ingest(self, req: Request) -> None:
        """Prefill ``prompt[:-1]`` monolithically on the PS and write its
        K/V into the request's pages.  The last prompt token is *not*
        prefilled: the request's first decode step feeds it, so the first
        sampled token comes from the same decode computation on every path
        (float, int8, fleet, monolithic)."""
        import jax
        import jax.numpy as jnp

        from repro.models import model as M
        P = req.prompt_len - 1
        if P <= 0:
            return
        fn = self._prefill_fns.get(P)
        if fn is None:
            cfg = self.cfg
            fn = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t})[1])
            self._prefill_fns[P] = fn
        cache = fn(self.params, jnp.asarray(req.prompt[None, :P]))
        vals = {nm: np.asarray(cache[nm][:, 0])
                for nm in self.kv.pools if nm in cache}
        self.kv.write_prompt(req.rid, vals)

    # ---------------------------------------------------------------- step --

    def step(self, fail_ids: Sequence[int] = (),
             fail_at_gemm: int = 0) -> Optional[ServeStepReport]:
        """One continuous-batching decode step (admit → decode one token per
        occupied slot through the fleet → scatter KV → retire).  Returns
        ``None`` when there is nothing to decode and nothing queued.
        The report's ``phases["step"]`` is the span around this whole call
        and lands on it as the call returns."""
        phases: Dict[str, float] = {}
        with span("cleave.serve.step", phases):
            return self._step(phases, fail_ids, fail_at_gemm)

    def _step(self, phases: Dict[str, float], fail_ids: Sequence[int],
              fail_at_gemm: int) -> Optional[ServeStepReport]:
        import jax.numpy as jnp

        from repro.models import model as M
        t0 = time.perf_counter()
        if not self.batcher.active:
            # idle fleet: fast-forward the virtual clock to the next arrival
            nxt = self.batcher.next_arrival()
            if nxt is None:
                return None
            self.clock = max(self.clock, nxt)
        with span("cleave.serve.admit", phases):
            admitted = self.batcher.admit(self.clock, self.wall)
            for req in admitted:
                self._ingest(req)
        active = [(b, r) for b, r in enumerate(self.batcher.slots)
                  if r is not None]
        if not active:
            return None

        B = self.slots
        tokens = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        rids: List[Optional[int]] = [None] * B
        for b, r in active:
            tokens[b, 0] = r.tokens[-1] if r.tokens else int(r.prompt[-1])
            pos[b] = r.next_pos
            rids[b] = r.rid
        views = self.kv.gather(rids, self.cache_len, phases=phases)
        with span("cleave.serve.kv_upload", phases):
            cache = {nm: jnp.asarray(v) for nm, v in views.items()}
            cache["pos"] = jnp.asarray(pos)

        with self.gemms.open() as fleet:
            if fail_ids:
                fleet.arm_failure(fail_ids, at_gemm=fail_at_gemm)
            # its self time, less the fleet GEMMs inside it, is PS ops
            with span("cleave.serve.decode", phases):
                logits, new_cache = M.decode_step(
                    self.cfg, self.params, cache, jnp.asarray(tokens),
                    scan_layers=False)
        records, churn_reports = self.gemms.drain()
        fired = tuple(sorted({int(i) for r in records
                              for i in r.failed_ids}))
        if fail_ids and not fired:
            raise RuntimeError(
                f"fail_at_gemm={fail_at_gemm} exceeds the step's "
                f"{len(records)} fleet GEMMs: the failure never fired")

        with span("cleave.serve.sample", phases):
            next_tok = np.asarray(
                jnp.argmax(logits[:, 0, :self.cfg.vocab_size], axis=-1))
        # scatter the active slots' new-token K/V back into their pages
        with span("cleave.serve.kv_write", phases):
            act = np.asarray([b for b, _ in active])
            act_pos = pos[act]
            bidx, sidx = jnp.asarray(act), jnp.asarray(act_pos)
            upd = {nm: np.asarray(new_cache[nm][:, bidx, sidx])
                   for nm in self.kv.pools}
            self.kv.write_tokens([rids[b] for b in act], act_pos, upd)
        if self.check_paged_read:
            self._check_paged_read(rids)

        priced = self.gemms.price_step(records)
        self.clock += priced
        wall = time.perf_counter() - t0
        self.wall += wall
        for b, r in active:
            r.tokens.append(int(next_tok[b]))
            r.token_times.append(self.clock)
            r.token_walls.append(self.wall)
        retired = self.batcher.retire(self.clock, self.wall)
        phases.update(sum_phases(records))

        report = ServeStepReport(
            step=self.step_index, n_active=len(active),
            n_admitted=len(admitted), n_retired=len(retired),
            wall_time=wall, priced_makespan=priced,
            n_gemms=len(records),
            n_tasks=sum(r.n_tasks for r in records),
            n_recovered=sum(r.n_recovered for r in records),
            verified=all(r.verified for r in records),
            plan_cache_hit_rate=(sum(r.plan_cached for r in records)
                                 / max(len(records), 1)),
            failed_ids=fired, records=records, phases=phases,
            host_operand_bytes=sum(r.host_operand_bytes for r in records))
        self.step_reports.append(report)
        self.rt.history.append({
            "event": "serve_step", "step": self.step_index,
            "n_active": report.n_active, "n_gemms": report.n_gemms,
            "n_recovered": report.n_recovered,
            "verified": report.verified,
            "priced_makespan": report.priced_makespan,
            "failed_ids": list(fired)})
        self.step_index += 1
        return report

    def run(self, max_steps: int = 10_000,
            fail_ids: Sequence[int] = (),
            fail_at_step: Optional[int] = None) -> "ServeReport":
        """Drive :meth:`step` until every submitted request finishes (or
        ``max_steps``).  ``fail_ids``/``fail_at_step`` injects a mid-run
        device failure into the ``fail_at_step``-th decode step."""
        for i in range(max_steps):
            inject = (fail_ids if fail_at_step is not None
                      and i == fail_at_step else ())
            if self.step(fail_ids=inject) is None:
                break
        else:
            if not self.batcher.idle:
                raise RuntimeError(
                    f"serve run did not drain in {max_steps} steps "
                    f"({self.batcher.n_pending} pending, "
                    f"{len(self.batcher.active)} active)")
        return self.report()

    # --------------------------------------------------------------- checks --

    def _check_paged_read(self, rids: List[Optional[int]]) -> None:
        """In-loop cross-check: the Pallas paged-KV kernel reading the
        pools **in place** (page-table scalar prefetch) must match dense
        attention over the gathered contiguous view — the TPU read path vs
        the PS read path, same pages."""
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops
        from repro.models.attention import decode_attention
        if self.cfg.mla:
            return   # the paged kernel reads K/V pools (GQA layout)
        pt, ln = self.kv.page_table_array(rids)
        if not ln.any():
            return
        kp, vp = self.kv.pools["k"], self.kv.pools["v"]
        if self.kv_int8:
            kp = (kp.astype(np.float32)
                  * self.kv.pools["k_scale"][..., None].astype(np.float32))
            vp = (vp.astype(np.float32)
                  * self.kv.pools["v_scale"][..., None].astype(np.float32))
        kp, vp = jnp.asarray(kp[0]), jnp.asarray(vp[0])     # layer 0 pools
        B, H, D = len(rids), self.cfg.n_heads, self.cfg.head_dim
        if self._check_q is None:
            rng = np.random.default_rng(0)
            self._check_q = jnp.asarray(
                rng.standard_normal((B, 1, H, D)).astype(np.float32))
        got = ops.gqa_flash_decode_paged(self._check_q, kp, vp,
                                         jnp.asarray(pt), jnp.asarray(ln))
        views = self.kv.gather(rids, self.cache_len)
        k = jnp.asarray(views["k"][0])
        v = jnp.asarray(views["v"][0])
        if self.kv_int8:
            k = k.astype(jnp.float32) \
                * jnp.asarray(views["k_scale"][0])[..., None]
            v = v.astype(jnp.float32) \
                * jnp.asarray(views["v_scale"][0])[..., None]
        valid = jnp.arange(self.cache_len)[None, :] < jnp.asarray(ln)[:, None]
        # rows with ln == 0 are fully masked in the oracle; skip them.  The
        # kernel contracts in float32, so the oracle must too (the TPU's
        # default float32 matmul rounds its operands to bfloat16)
        with jax.default_matmul_precision("highest"):
            want = decode_attention(self._check_q, k, v, valid)
        live = np.asarray(ln) > 0
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live],
                                   rtol=2e-4, atol=2e-4)
        self.paged_read_checks += 1

    # --------------------------------------------------------------- report --

    def report(self) -> ServeReport:
        """Latency aggregate over the finished requests (module docstring:
        measured wall and engine-priced virtual clock, side by side)."""
        fin = self.batcher.finished
        tok_lat_m: List[float] = []
        tok_lat_v: List[float] = []
        e2e_m: List[float] = []
        e2e_v: List[float] = []
        n_tokens = 0
        for r in fin:
            n_tokens += len(r.tokens)
            # the virtual first-token latency baselines at *arrival*, not
            # admission: under backlog (more streams than slots) the queue
            # wait dominates TTFT and spreads the priced percentiles —
            # baselining at admit collapses every request onto the same
            # steady-state step price (p50 == p99, degenerate).  The wall
            # clock keeps the admit baseline: arrivals are virtual-only.
            prev_w, prev_v = r.admit_wall, r.arrival
            for tw, tv in zip(r.token_walls, r.token_times):
                tok_lat_m.append(tw - prev_w)
                tok_lat_v.append(tv - prev_v)
                prev_w, prev_v = tw, tv
            e2e_m.append(r.finish_wall - r.admit_wall)
            e2e_v.append(r.finish_time - r.arrival)
        for r in self.batcher.active:       # in-flight tokens still count
            n_tokens += len(r.tokens)

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else 0.0

        recs = [rec for rep in self.step_reports for rec in rep.records]
        failed = tuple(sorted({int(i) for rep in self.step_reports
                               for i in rep.failed_ids}))
        phases: Dict[str, float] = {}
        for rep in self.step_reports:
            for k, v in rep.phases.items():
                phases[k] = phases.get(k, 0.0) + v
        return ServeReport(
            n_requests=len(fin), n_tokens=n_tokens,
            n_steps=self.step_index,
            wall_time=self.wall, virtual_time=self.clock,
            tokens_per_sec=n_tokens / max(self.wall, 1e-12),
            tokens_per_sec_priced=n_tokens / max(self.clock, 1e-12),
            token_lat_p50=pct(tok_lat_m, 50),
            token_lat_p99=pct(tok_lat_m, 99),
            token_lat_p50_priced=pct(tok_lat_v, 50),
            token_lat_p99_priced=pct(tok_lat_v, 99),
            e2e_p50=pct(e2e_m, 50), e2e_p99=pct(e2e_m, 99),
            e2e_p50_priced=pct(e2e_v, 50), e2e_p99_priced=pct(e2e_v, 99),
            plan_cache_hit_rate=(sum(r.plan_cached for r in recs)
                                 / max(len(recs), 1)),
            n_recovered=sum(r.n_recovered for r in recs),
            failed_ids=failed, cache=self.kv.stats(), phases=phases,
            host_operand_bytes=sum(rep.host_operand_bytes
                                   for rep in self.step_reports))
