"""Batched serving driver: prefill a batch of prompts, then decode with the
KV cache (greedy or temperature sampling).  CPU-scale runner for the same
``serve_step`` the decode dry-run shapes lower.

``--edge-plan N`` additionally drives the **fleet decode path**: the same
prompts run through ``CleaveRuntime.serve_session`` — paged KV on the PS,
every projection GEMM executed on an N-device edge fleet — with the
planner's projection and the engine-priced per-token latency printed as the
predicted column next to the measured one (docs/SERVING.md).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --batch 4 --prompt-len 16 --gen 32 [--kv-int8] [--edge-plan 16]
  (``--no-reduced`` selects the full-size config.)
"""
from __future__ import annotations

import argparse
import time


def decode(cfg, params, prompts, gen: int, *, kv_int8: bool = False,
           temperature: float = 0.0, key=None, encoder_feats=None):
    """Monolithic decode: prefill ``prompts`` (B, P) in one pass, then
    ``gen`` jitted decode steps against the KV cache.  Returns
    ``(tokens, t_prefill, t_token)``: the (B, gen) generated ids, the
    prefill wall time and the mean wall time per decode step, in seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M

    if key is None:
        key = jax.random.PRNGKey(0)
    B, P = prompts.shape
    batch = {"tokens": prompts}
    if cfg.enc_dec:
        batch["encoder_feats"] = encoder_feats
    t0 = time.perf_counter()
    logits, pre_cache = M.prefill(cfg, params, batch)
    t_prefill = time.perf_counter() - t0

    cache = M.init_cache(cfg, B, P + gen,
                         enc_len=(2 * P if cfg.enc_dec else 0),
                         kv_quant=kv_int8)
    for nm in ("k", "v", "ckv", "kpe"):
        if nm in cache and nm in pre_cache and not kv_int8:
            cache[nm] = cache[nm].at[:, :, :P].set(
                pre_cache[nm].astype(cache[nm].dtype))
    for nm in ("wkv_state", "tm_prev", "cm_prev"):
        if nm in pre_cache:
            cache[nm] = pre_cache[nm]
    if cfg.enc_dec:
        from repro.models import encdec
        ck, cv = encdec.prepare_cross_cache(cfg, params, encoder_feats)
        cache["cross_k"], cache["cross_v"] = ck, cv
    if kv_int8:
        # re-ingest the prompt token by token (quantized writes)
        cache["pos"] = jnp.zeros((), jnp.int32)
        step_fn = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t))
        for t in range(P):
            logits, cache = step_fn(params, cache, prompts[:, t:t + 1])
    else:
        cache["pos"] = pre_cache["pos"]

    step_fn = jax.jit(lambda p, c, t: M.decode_step(cfg, p, c, t))

    def sample(lg, k):
        lg = lg[:, -1, :cfg.vocab_size]
        if temperature <= 0:
            return jnp.argmax(lg, axis=-1)[:, None]
        return jax.random.categorical(k, lg / temperature)[:, None]

    tok = sample(logits, key)
    out = [np.asarray(tok)]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        key, sk = jax.random.split(key)
        logits, cache = step_fn(params, cache, tok.astype(jnp.int32))
        tok = sample(logits, sk)
        out.append(np.asarray(tok))
    t_token = (time.perf_counter() - t0) / max(gen - 1, 1)
    return np.concatenate(out, axis=1), t_prefill, t_token


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default; --no-reduced for "
                         "full size)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--edge-plan", type=int, default=0, metavar="N",
                    help="plan AND execute the decode through an N-device "
                         "edge fleet (CleaveRuntime.serve_session): paged "
                         "KV on the PS, projection GEMMs on the fleet, "
                         "engine-priced latency as the predicted column")
    ap.add_argument("--page-size", type=int, default=16,
                    help="edge path: tokens per KV page")
    ap.add_argument("--fleet-exec", default="numpy",
                    choices=("numpy", "jax"),
                    help="edge path: fleet executor substrate (numpy: "
                         "float64 host stand-in; jax: Pallas/XLA batched "
                         "kernels on the default device)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.models import model as M

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    B, P, G = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    feats = (jax.random.normal(key, (B, 2 * P, cfg.d_model))
             if cfg.enc_dec else None)
    gen, t_prefill, dt = decode(cfg, params, prompts, G,
                                kv_int8=args.kv_int8,
                                temperature=args.temperature, key=key,
                                encoder_feats=feats)
    print(f"arch={cfg.name} prefill={t_prefill * 1000:.0f}ms "
          f"decode={dt * 1000:.1f}ms/tok kv_int8={args.kv_int8}")
    for b in range(min(B, 2)):
        print(f"  req{b}: {gen[b, :24].tolist()}")

    if args.edge_plan > 0:
        from repro.api import CleaveRuntime, Fleet, PlanRequest
        rt = CleaveRuntime(arch=cfg,
                           fleet=Fleet.sample(args.edge_plan,
                                              seed=args.seed),
                           accounting="broadcast")
        # predicted column #1: the forward-only batch plan over the fleet
        rep = rt.plan(request=PlanRequest(batch=B, seq=P + G,
                                          backward=False))
        print(f"edge serve plan ({args.edge_plan} devices): "
              f"batch_time={rep.batch_time:.1f}s "
              f"comm/dev={rep.per_device_comm / 1e6:.0f}MB "
              f"mem/dev={rep.per_device_mem / 1e6:.0f}MB")
        # and now execute: same prompts, same params, decode through the
        # fleet under continuous batching
        sess = rt.serve_session(params, slots=B,
                                page_size=args.page_size,
                                max_len=P + G, kv_int8=args.kv_int8,
                                backend=args.fleet_exec, seed=args.seed)
        pn = np.asarray(prompts)
        for b in range(B):
            sess.submit(pn[b], max_new=G)
        srep = sess.run()
        print(f"edge serve executed: {srep.n_tokens} toks in "
              f"{srep.n_steps} steps | measured "
              f"{srep.wall_time / max(srep.n_tokens, 1) * 1e3:.1f}ms/tok "
              f"({srep.tokens_per_sec:.1f} tok/s) | predicted "
              f"{srep.virtual_time / max(srep.n_tokens, 1) * 1e3:.1f}ms/tok "
              f"({srep.tokens_per_sec_priced:.1f} tok/s) | plan cache "
              f"{srep.plan_cache_hit_rate:.0%}")
        print(f"  {srep.log_line()}")
        if args.temperature <= 0:
            fleet_toks = [r.tokens for r in sess.batcher.finished]
            mono_toks = [gen[b, :G].tolist() for b in range(B)]
            match = sorted(map(tuple, fleet_toks)) \
                == sorted(map(tuple, mono_toks))
            print(f"  greedy tokens match monolithic: {match}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
