"""End-to-end training driver.

CPU-scale real training (examples/train_e2e.py uses this) and the
production-mesh entry point.  Wires the synthetic data pipeline, the model
zoo, AdamW, periodic checkpointing, and (when devices allow) the production
mesh + CLEAVE 2-D shardings.

``--backend fleet`` runs every training step PS-centrically through the
:class:`~repro.api.CleaveRuntime` fleet executors (§3.2): each projection
GEMM — forward and backward — is planned, dispatched, Freivalds-verified,
and (under ``--fail-step``) churn-recovered on a simulated edge fleet,
while the PS hosts the non-GEMM ops and AdamW.  Loss and parameters match
the monolithic jitted step to ≤1e-4 relative (see docs/TRAINING.md).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 100 --batch 8 --seq 128 [--ckpt-dir ckpts]
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --backend fleet --fleet-devices 16 --steps 5 --batch 2 --seq 32 \
      --fail-step 2 --fail-ids 3,7
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np


def run(argv=None):
    """Parse ``argv`` and train.  Returns ``(params, history)``: the final
    parameters and one metrics row per step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 (host devices)")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--backend", default="jax", choices=("jax", "fleet"),
                    help="jax: monolithic jitted step; fleet: every "
                         "projection GEMM executes on a simulated edge "
                         "fleet via the CleaveRuntime session (PS-centric "
                         "training, §3.2)")
    ap.add_argument("--fleet-devices", type=int, default=16,
                    help="fleet size for --backend fleet")
    ap.add_argument("--fleet-exec", default="numpy",
                    choices=("numpy", "jax"),
                    help="fleet executor substrate (numpy: float64 host "
                         "stand-in; jax: Pallas/XLA batched kernels)")
    ap.add_argument("--fleet-kernel", default="auto",
                    help="jax substrate kernel: auto | pallas | xla")
    ap.add_argument("--fail-step", type=int, default=None,
                    help="inject a device failure during this step "
                         "(--backend fleet): the in-flight GEMM recovers "
                         "via churn.recover, the devices are evicted, "
                         "cached plans are patched")
    ap.add_argument("--fail-ids", default="",
                    help="comma-separated device ids for --fail-step")
    ap.add_argument("--fail-at-gemm", type=int, default=0,
                    help="GEMM index within --fail-step at which the "
                         "failure strikes")
    ap.add_argument("--edge-plan", type=int, default=0, metavar="N",
                    help="before training, plan this config's batch over an "
                         "N-device edge fleet via the CleaveRuntime session "
                         "API and print the projected batch time")
    ap.add_argument("--edge-accounting", default="broadcast",
                    choices=("unicast", "broadcast"))
    args = ap.parse_args(argv)

    import jax
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.steps import make_train_step
    from repro.models import model as M
    from repro.optim import adam
    from repro.parallel.sharding import make_rules

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    over = {}
    if args.layers:
        over["n_layers"] = args.layers
    if args.d_model:
        over["d_model"] = args.d_model
        over["d_ff"] = 4 * args.d_model
    if args.vocab:
        over["vocab_size"] = args.vocab
    if over:
        cfg = dataclasses.replace(cfg, **over)

    if args.edge_plan > 0:
        from repro.api import CleaveRuntime, Fleet
        rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(args.edge_plan,
                                                        seed=args.seed),
                           accounting=args.edge_accounting)
        rep = rt.plan(batch=args.batch, seq=args.seq)
        print(f"edge plan ({args.edge_plan} devices, "
              f"{rep.accounting}): batch_time={rep.batch_time:.1f}s "
              f"comm/dev={rep.per_device_comm / 1e6:.0f}MB "
              f"mem/dev={rep.per_device_mem / 1e6:.0f}MB "
              f"solved {rep.cache_misses} shapes in {rep.solve_time:.2f}s")

    rules = None
    if args.mesh:
        if args.backend == "fleet":
            raise SystemExit("--mesh and --backend fleet are exclusive: "
                             "the fleet IS the device layer")
        from repro.launch.mesh import make_mesh
        dims = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(dims, ("data", "model")[-len(dims):])
        rules = make_rules(mesh, mode="train")

    opt_cfg = adam.AdamConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                              total_steps=args.steps)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    opt_state = adam.init(params, opt_cfg)
    out_shardings = None
    if rules is not None:
        # place params and moments with the CLEAVE 2-D shardings, and keep
        # them there across steps (donated buffers must alias)
        from repro.launch import specs as SP
        p_specs = SP.param_specs(cfg, rules)
        psh = jax.tree.map(lambda s: s.sharding, p_specs)
        osh = jax.tree.map(lambda s: s.sharding,
                           SP.opt_specs(p_specs, rules))
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(opt_state, osh)
        out_shardings = (psh, osh, None)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,} vocab={cfg.vocab_size} "
          f"layers={cfg.n_layers} d={cfg.d_model}")

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch,
                                  seed=args.seed))
    fleet_session = None
    fail_ids = [int(i) for i in args.fail_ids.split(",") if i.strip()]
    if args.fail_step is not None and not fail_ids:
        raise SystemExit("--fail-step needs --fail-ids (comma-separated "
                         "device ids to fail)")
    if (args.fail_step is not None or fail_ids) \
            and args.backend != "fleet":
        raise SystemExit("--fail-step/--fail-ids inject fleet device "
                         "failures; pass --backend fleet")
    if args.fail_step is not None and args.fail_step >= args.steps:
        raise SystemExit(f"--fail-step {args.fail_step} never runs: the "
                         f"run has only {args.steps} step(s)")
    if args.backend == "fleet":
        from repro.api import CleaveRuntime, Fleet
        rt = CleaveRuntime(arch=cfg,
                           fleet=Fleet.sample(args.fleet_devices,
                                              seed=args.seed),
                           accounting=args.edge_accounting)
        fleet_session = rt.train_session(
            opt_cfg, backend=args.fleet_exec, kernel=args.fleet_kernel,
            q_chunk=64, k_chunk=64, loss_chunk=64)
        print(f"fleet backend: {len(rt.fleet)} devices "
              f"({args.fleet_exec} executor), accounting="
              f"{args.edge_accounting}")
        step_fn = None
    else:
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, rules=rules,
                                          q_chunk=64, k_chunk=64,
                                          loss_chunk=64),
                          donate_argnums=(0, 1), out_shardings=out_shardings)

    mgr = None
    if args.ckpt_dir:
        from repro.checkpointing.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)

    history = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        t_step = time.perf_counter()
        batch = {k: jax.numpy.asarray(v)
                 for k, v in data.batch(step).items()}
        if cfg.modality == "vision":
            rngv = np.random.default_rng((args.seed, step, 7))
            svis = max(args.seq // 4, 1)
            batch["vision_embeds"] = jax.numpy.asarray(
                rngv.standard_normal((args.batch, svis, cfg.d_model)),
                dtype=cfg.dtype)
        if cfg.enc_dec:
            rnga = np.random.default_rng((args.seed, step, 11))
            batch["encoder_feats"] = jax.numpy.asarray(
                rnga.standard_normal((args.batch, 2 * args.seq,
                                      cfg.d_model)), dtype=cfg.dtype)
        if fleet_session is not None:
            fid = fail_ids if step == args.fail_step else ()
            params, opt_state, metrics = fleet_session.step(
                params, opt_state, batch, fail_ids=fid,
                fail_at_gemm=args.fail_at_gemm)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        row = {"step": step, "loss": loss,
               "grad_norm": float(metrics["grad_norm"]),
               "lr": float(metrics["lr"]),
               "step_time": time.perf_counter() - t_step}
        if fleet_session is not None:
            rep = metrics["fleet"]
            row.update(fleet_gemms=rep.n_gemms, fleet_tasks=rep.n_tasks,
                       fleet_recovered=rep.n_recovered,
                       fleet_verified=rep.verified,
                       fleet_exec_time=rep.fleet_exec_time,
                       fleet_predicted_makespan=rep.predicted_makespan,
                       fleet_cache_hit_rate=rep.plan_cache_hit_rate)
        history.append(row)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({dt / (step + 1):.2f}s/step)")
            if fleet_session is not None:
                print(f"           {metrics['fleet'].log_line()}")
        if mgr is not None:
            mgr.maybe_save(step, {"params": params, "opt": opt_state},
                           {"loss": loss})
        assert np.isfinite(loss), f"loss diverged at step {step}"

    first = np.mean([h["loss"] for h in history[:5]])
    last = np.mean([h["loss"] for h in history[-5:]])
    print(f"loss: first5={first:.4f} last5={last:.4f} "
          f"improved={first - last:.4f}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    return params, history


def main(argv=None):
    run(argv)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
