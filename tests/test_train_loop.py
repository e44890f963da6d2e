"""PS-centric training parity: the fleet-executed train step
(``CleaveRuntime.train_step`` / ``repro.train_loop``) must reproduce the
monolithic jitted ``launch.steps.make_train_step`` — loss and parameters
within 1e-4 relative over several steps — on both executor backends, and
stay exact under a mid-step injected device failure (``churn.recover``).
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.api import CleaveRuntime, Fleet  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticLM  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim import adam  # noqa: E402

B, S = 2, 32
CHUNKS = dict(q_chunk=16, k_chunk=16, loss_chunk=16)
REL_TOL = 1e-4


def _setup(seed=0, n_devices=8):
    cfg = get_config("llama3-8b").reduced()
    opt_cfg = adam.AdamConfig(lr=3e-4, warmup_steps=2, total_steps=20)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    opt = adam.init(params, opt_cfg)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                                  global_batch=B, seed=seed))
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(n_devices, seed=seed))
    return cfg, opt_cfg, params, opt, data, rt


def _batch(data, step):
    return {k: jnp.asarray(v) for k, v in data.batch(step).items()}


def _worst_rel(tree_a, tree_b):
    return max(
        float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-12))
        for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)))


def _run_parity(n_steps, *, backend="numpy", kernel="auto",
                fail_step=None, fail_ids=(), fail_at_gemm=0):
    cfg, opt_cfg, params, opt, data, rt = _setup()
    mono = jax.jit(make_train_step(cfg, opt_cfg, **CHUNKS))
    p_m, o_m = params, opt
    p_f, o_f = params, opt
    reports = []
    for step in range(n_steps):
        batch = _batch(data, step)
        p_m, o_m, met_m = mono(p_m, o_m, batch)
        fid = fail_ids if step == fail_step else ()
        p_f, o_f, met_f = rt.train_step(
            p_f, o_f, batch, opt_cfg=opt_cfg, backend=backend,
            kernel=kernel, fail_ids=fid, fail_at_gemm=fail_at_gemm,
            **CHUNKS)
        lm, lf = float(met_m["loss"]), float(met_f["loss"])
        assert abs(lm - lf) / abs(lm) <= REL_TOL, (step, lm, lf)
        reports.append(met_f["fleet"])
    assert _worst_rel(p_m, p_f) <= REL_TOL
    assert _worst_rel(o_m.mu, o_f.mu) <= REL_TOL
    return rt, reports


# ------------------------------------------------------------------ parity --

def test_parity_numpy_backend():
    rt, reports = _run_parity(3, backend="numpy")
    for rep in reports:
        assert rep.verified
        assert rep.n_gemms > 0 and rep.n_tasks > 0
        assert rep.predicted_makespan > 0.0
        assert rep.gemm_flops > 0.0
    # warm steps serve every plan from the cache
    assert reports[-1].plan_cache_hit_rate == 1.0
    # runtime history logged every step
    evs = [h for h in rt.history if h["event"] == "train_step"]
    assert len(evs) == 3 and evs[-1]["verified"]


def test_parity_jax_backend_one_step():
    # kernel="xla" is the compiled CPU path (Pallas interpret parity is
    # covered by test_jax_executor); one step bounds tier-1 compile cost
    _, reports = _run_parity(1, backend="jax", kernel="xla")
    assert reports[0].verified and reports[0].n_gemms > 0


def test_parity_with_mid_step_failure():
    rt, reports = _run_parity(3, fail_step=1, fail_ids=[3], fail_at_gemm=5)
    rep = reports[1]
    assert rep.failed_ids == (3,)
    assert rep.n_recovered > 0          # churn.recover re-executed tasks
    assert rep.n_plans_patched > 0      # cached plans carried to survivors
    assert len(rt.fleet) == 7           # device evicted for good
    assert 3 not in rt.fleet.ids()
    # the failure never reaches the numerics: later steps stay clean
    assert reports[2].n_recovered == 0 and reports[2].verified


def test_fail_unknown_device_rejected():
    _, _, params, opt, data, rt = _setup()
    with pytest.raises(ValueError, match="unknown devices"):
        rt.train_step(params, opt, _batch(data, 0), fail_ids=[999],
                      **CHUNKS)


def test_fail_beyond_step_gemm_count_rejected():
    # an armed failure that never fires must be an error, not a silent
    # no-op that still stamps failed_ids on the report
    _, opt_cfg, params, opt, data, rt = _setup()
    session = rt.train_session(opt_cfg)
    with pytest.raises(RuntimeError, match="never fired"):
        session.step(params, opt, _batch(data, 0), fail_ids=[3],
                     fail_at_gemm=10_000)
    assert len(rt.fleet) == 8        # nothing was evicted
    # the session remains usable and reports no failure
    _, _, met = session.step(params, opt, _batch(data, 0))
    assert met["fleet"].failed_ids == ()


# ------------------------------------------------------------ hook plumbing --

def test_pdot_is_plain_matmul_without_hook():
    from repro.models import layers as L
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 8)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((8, 3)),
                    jnp.float32)
    np.testing.assert_array_equal(np.asarray(L.pdot(x, w)),
                                  np.asarray(x @ w))


def test_hooks_do_not_nest():
    from repro.train_loop import hook
    with hook.use_hook(lambda x, w: x @ w):
        with pytest.raises(RuntimeError, match="already installed"):
            with hook.use_hook(lambda x, w: x @ w):
                pass
    assert hook.active() is None


def test_fleet_gemm_needs_an_open_session():
    """No path computes a fleet GEMM without a session: the host side of
    the fleet dot raises instead of falling back to a plain product."""
    from repro.train_loop import fleet_gemm
    a = np.ones((4, 8), np.float32)
    b = np.ones((8, 3), np.float32)
    with pytest.raises(RuntimeError, match="FleetGemmSession"):
        fleet_gemm._host_gemm("fwd", a, b)


def test_fleet_gemm_refuses_traced_operands():
    """Fleet GEMMs run eagerly on concrete operands; a jit around the
    hooked step is an error, not a host callback."""
    _, opt_cfg, params, _, _, rt = _setup()
    from repro.models import layers as L
    from repro.train_loop.fleet_gemm import FleetGemmSession
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 3), jnp.float32)
    with FleetGemmSession(rt).open():
        np.testing.assert_allclose(np.asarray(L.pdot(x, w)), 8.0)
        with pytest.raises(TypeError, match="eagerly"):
            jax.jit(L.pdot)(x, w)


def test_bf16_fleet_jax_backend_matches_numpy_executor():
    """bf16 params at small widths ``reduced()`` would force to float32:
    two fleet steps on the jax executor, whose PadCache fingerprints the
    bf16 operands, reach the numpy executor's losses.  Both executors
    round every GEMM output to bf16; they differ only in accumulating in
    float32 vs float64 before that rounding, so the losses agree to well
    inside one bf16 unit roundoff (2^-8)."""
    cfg = dataclasses.replace(get_config("opt-1.3b"), n_layers=2,
                              d_model=128, n_heads=4, n_kv_heads=4,
                              d_ff=344, vocab_size=512)
    assert cfg.dtype == cfg.param_dtype == "bfloat16"
    opt_cfg = adam.AdamConfig(lr=3e-4, warmup_steps=1, total_steps=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                                  global_batch=B, seed=0))
    losses = {}
    for backend in ("numpy", "jax"):
        rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(8, seed=0))
        sess = rt.train_session(opt_cfg, backend=backend, **CHUNKS)
        p, o = params, adam.init(params, opt_cfg)
        losses[backend] = []
        for step in range(2):
            p, o, met = sess.step(p, o, _batch(data, step))
            assert met["fleet"].verified and met["fleet"].n_gemms > 0
            assert p["embed"]["tok"].dtype == jnp.bfloat16
            losses[backend].append(float(met["loss"]))
    assert np.all(np.isfinite(losses["jax"]))
    np.testing.assert_allclose(losses["jax"], losses["numpy"], rtol=2e-3)


def test_unrolled_forward_matches_scan():
    cfg, _, params, _, data, _ = _setup()
    batch = _batch(data, 0)
    loss_scan, _ = M.loss_fn(cfg, params, batch, scan_layers=True, **CHUNKS)
    loss_unroll, _ = M.loss_fn(cfg, params, batch, scan_layers=False,
                               **CHUNKS)
    assert abs(float(loss_scan) - float(loss_unroll)) \
        / abs(float(loss_scan)) <= 1e-6


def test_step_exception_resets_session():
    cfg, opt_cfg, params, opt, data, rt = _setup()
    session = rt.train_session(opt_cfg)
    batch = _batch(data, 0)
    bad = dict(batch)
    bad["labels"] = batch["labels"][:, :-1]   # blows up after GEMMs ran
    with pytest.raises(Exception):
        session.step(params, opt, bad, fail_ids=[3], fail_at_gemm=10_000)
    # the aborted step's records and armed failure must not leak
    assert session.gemms.records == []
    assert session.gemms._armed is None
    p, o, met = session.step(params, opt, batch)
    rep = met["fleet"]
    assert rep.n_gemms > 0 and rep.n_recovered == 0 and not rep.failed_ids


def test_session_reuse_and_price_caching():
    cfg, opt_cfg, params, opt, data, rt = _setup()
    p, o = params, opt
    for step in range(2):
        p, o, met = rt.train_step(p, o, _batch(data, step),
                                  opt_cfg=opt_cfg, **CHUNKS)
    # one session object serves both steps (warm plan cache)
    assert len(rt._train_sessions) == 1
    session = next(iter(rt._train_sessions.values()))
    assert session.step_index == 2
    assert len(session.reports) == 2
    assert session.reports[1].plan_cache_hit_rate == 1.0
    # predicted makespan identical while the fleet is unchanged
    assert session.reports[0].predicted_makespan \
        == session.reports[1].predicted_makespan


# ------------------------------------------------------------- checkpoint ---

def test_checkpoint_save_restore_resume_bit_matches(tmp_path):
    """Kill-and-resume regression: train 2 steps with periodic PS-side
    checkpoints, restore in a fresh session, resume 2 more — the resumed
    trajectory (losses, lr schedule via the Adam step counter, final
    parameters) must bit-match the uninterrupted 4-step run."""
    cfg, opt_cfg, params, opt, data, rt = _setup()
    ref = rt.train_session(opt_cfg, **CHUNKS)
    p_r, o_r = params, opt
    ref_losses = []
    for step in range(4):
        p_r, o_r, met = ref.step(p_r, o_r, _batch(data, step))
        ref_losses.append(float(met["loss"]))

    # session A: checkpoint every 2 steps, killed after step 2
    *_, rt_a = _setup()
    sess_a = rt_a.train_session(opt_cfg, checkpoint=str(tmp_path),
                                checkpoint_every=2, **CHUNKS)
    p, o = params, opt
    for step in range(2):
        p, o, met = sess_a.step(p, o, _batch(data, step))
        assert float(met["loss"]) == ref_losses[step]
    assert sess_a.checkpoint.steps() == [2]

    # session B: fresh process, restores the snapshot and resumes
    *_, rt_b = _setup()
    sess_b = rt_b.train_session(opt_cfg, checkpoint=str(tmp_path),
                                checkpoint_every=2, **CHUNKS)
    p2, o2, step0 = sess_b.restore(params, opt)
    assert step0 == 2 and sess_b.step_index == 2
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(p), jax.tree.leaves(p2)))
    for step in range(2, 4):
        p2, o2, met = sess_b.step(p2, o2, _batch(data, step))
        assert float(met["loss"]) == ref_losses[step]
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(p_r), jax.tree.leaves(p2)))
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(o_r), jax.tree.leaves(o2)))
    # the resumed session kept the cadence: next boundary saved at step 4
    assert sess_b.checkpoint.steps() == [2, 4]


def test_checkpoint_restore_empty_dir_passes_through(tmp_path):
    cfg, opt_cfg, params, opt, data, rt = _setup()
    sess = rt.train_session(opt_cfg, checkpoint=str(tmp_path), **CHUNKS)
    p, o, step = sess.restore(params, opt)
    assert step == 0 and p is params and o is opt
    bare = rt.train_session(opt_cfg, **CHUNKS)
    with pytest.raises(RuntimeError):
        bare.restore(params, opt)


# ------------------------------------------------------------------- slow ---

@pytest.mark.slow
def test_parity_numpy_six_steps_with_churn():
    """Nightly: longer horizon, failure mid-run, parity must hold to the
    final parameters."""
    rt, reports = _run_parity(6, fail_step=2, fail_ids=[1, 5],
                              fail_at_gemm=11)
    assert len(rt.fleet) == 6
    assert all(r.verified for r in reports)


@pytest.mark.slow
def test_parity_jax_backend_three_steps():
    _, reports = _run_parity(3, backend="jax", kernel="xla")
    assert all(r.verified for r in reports)
