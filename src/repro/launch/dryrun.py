import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    # The CPU backend emulates bf16 dots in f32; while-loop invariant code
    # motion then hoists whole-array converts of scanned weights/caches out
    # of the layer loop, carrying full f32 shadows (2-4x memory) that do not
    # exist on TPU (native bf16 MXU).  Disable the pass for faithful
    # memory_analysis numbers.
    + " --xla_disable_hlo_passes=while-loop-invariant-code-motion"
    + " " + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
against the production meshes, prove per-device memory fits, and extract the
roofline terms (FLOPs, bytes, collective bytes) from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k [--multi-pod] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The placeholder-device count (512) is set in the first lines above, before
any jax import — jax locks the device count on first init.  Tests/benches
never import this module with defaults (they see 1 device).
"""
import argparse
import json
import re
import sys
import time


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (post-SPMD) HLO.

    Returns {op_kind: {"count": n, "bytes": total_operand_bytes}} where bytes
    are the per-shard tensor sizes as written in the HLO (i.e. bytes moved
    per device per op application)."""
    dt_bytes = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: {"count": 0, "bytes": 0.0} for k in kinds}
    # e.g.:  %all-gather.3 = bf16[16,4096,512]{...} all-gather(...)
    shape_re = re.compile(
        r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\b"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start|-done)?\(")
    for m in shape_re.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in dt_bytes:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind]["count"] += 1
        out[kind]["bytes"] += n * dt_bytes[dt]
    return out


def while_trip_counts(hlo_text: str):
    """Total trip count hints from HLO while loops (scan over layers etc.),
    used to annotate that cost_analysis counts loop bodies once."""
    return [int(x) for x in re.findall(
        r'"known_trip_count":\{"n":"(\d+)"\}', hlo_text)]


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) for training;
    2·N_active·tokens for inference steps."""
    n_active = cfg.active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # one token


def run_one(arch: str, shape_name: str, multi_pod: bool,
            mode_override: str = None, save_hlo: str = None,
            mesh_override: str = None, fsdp: bool = False,
            kv_quant: bool = False) -> dict:
    import jax
    from repro.configs.base import INPUT_SHAPES, get_config
    from repro.launch import steps as ST
    from repro.launch.mesh import HW, make_mesh, make_production_mesh
    from repro.parallel.sharding import make_rules

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if mesh_override:
        dims = tuple(int(x) for x in mesh_override.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    mode = mode_override or {"train": "train", "prefill": "prefill",
                             "decode": "decode"}[shape.kind]
    # big models can't replicate weights across the 'data' axis even at
    # serve time: use CLEAVE 2-D row x column weight sharding
    weight_2d = (mode == "train") or cfg.n_params() > 30e9
    rules = make_rules(mesh, mode=mode, weight_2d=weight_2d, fsdp=fsdp)

    t0 = time.perf_counter()
    fn, arg_specs, donate, out_sh = ST.step_and_specs(cfg, shape, rules,
                                                      kv_quant=kv_quant)
    with mesh:
        jitted = jax.jit(fn, donate_argnums=donate, out_shardings=out_sh)
        lowered = jitted.lower(*arg_specs)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    trips = while_trip_counts(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    # xla cost_analysis counts while bodies once; use the trip-count-aware
    # static analyzer for the roofline terms (per device, post-SPMD shapes).
    from repro.launch import hlo_analysis
    costs = hlo_analysis.analyze(hlo)
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    hlo_flops = costs.flops
    hlo_bytes = costs.hbm_bytes
    coll = costs.collectives
    coll_bytes = costs.collective_bytes
    mf = model_flops(cfg, shape)

    t_compute = hlo_flops / HW["peak_flops_bf16"]
    t_memory = hlo_bytes / HW["hbm_bw"]
    t_collective = coll_bytes / HW["ici_bw_per_link"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)

    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "n_chips": n_chips,
        "mode": mode,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
            "fits_hbm": (mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes
                         - mem.alias_size_in_bytes) < HW["hbm_bytes"],
        },
        "cost": {"hlo_flops": hlo_flops, "hlo_bytes": hlo_bytes,
                 "xla_flops_uncorrected": xla_flops,
                 "xla_bytes_uncorrected": xla_bytes},
        "collectives": coll,
        "collective_bytes": coll_bytes,
        "while_trip_counts": trips,
        "model_flops": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / hlo_flops if hlo_flops else None,
        "roofline": terms,
        "dominant": dominant,
        "params": cfg.n_params(),
        "active_params": cfg.active_params(),
    }
    return out


SKIPS = {}   # no (arch, shape) skips: sliding-window/native variants cover
             # long_500k for every family (DESIGN.md §5)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--mode", default=None, help="sharding-rule override")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--mesh", default=None,
                    help="override mesh dims, e.g. 4x2 or 2x4x2 (dev only)")
    ap.add_argument("--fsdp", action="store_true",
                    help="store weights 2-D, gather per layer (§Perf)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache for decode shapes (§Perf)")
    args = ap.parse_args(argv)

    from repro.configs.base import INPUT_SHAPES

    combos = []
    if args.all:
        from repro.configs.base import list_configs
        assigned = [a for a in list_configs()
                    if not a.startswith(("opt-", "llama2-"))]
        for a in assigned:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    results = []
    for arch, shape in combos:
        if (arch, shape) in SKIPS:
            print(f"SKIP {arch} {shape}: {SKIPS[(arch, shape)]}")
            continue
        try:
            r = run_one(arch, shape, args.multi_pod, args.mode,
                        args.save_hlo, args.mesh, args.fsdp, args.kv_int8)
            results.append(r)
            print(f"OK   {arch:24s} {shape:12s} mesh={r['mesh']} "
                  f"compile={r['compile_s']:7.1f}s "
                  f"mem/dev={r['memory']['peak_per_device']/1e9:6.2f}GB "
                  f"fits={r['memory']['fits_hbm']} "
                  f"dominant={r['dominant']}")
            print(json.dumps({k: r[k] for k in
                              ("memory", "cost", "collective_bytes",
                               "roofline", "useful_flops_ratio")},
                             indent=None, default=str))
        except Exception as e:  # noqa
            print(f"FAIL {arch} {shape}: {type(e).__name__}: {e}")
            results.append({"arch": arch, "shape": shape, "error": str(e)})
            if not args.all:
                raise
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
    bad = [r for r in results if "error" in r]
    print(f"\n{len(results) - len(bad)}/{len(results)} combos compiled")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
