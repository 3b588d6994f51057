import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x input shape) on the
production meshes, print memory/cost analysis, extract roofline terms.

MUST be run as its own process (python -m repro.launch.dryrun ...): the device
count is locked into jax at first init, hence the env assignment above before
any jax import.

Results accumulate in dryrun_results.json (one entry per arch/shape/mesh/tag) so
interrupted sweeps resume, and benchmarks/roofline.py renders the table.
"""
import argparse
import json
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import INPUT_SHAPES
from repro.core import cost_model
from repro.launch import hlo_analysis, steps
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.models.model import build_model
from repro.models.specs import ShardingPolicy
from repro.obs import clock

RESULTS = Path(__file__).resolve().parents[3] / "dryrun_results.json"

# documented skips (DESIGN.md §Shape coverage)
SKIPS = {("whisper-large-v3", "long_500k"):
         "enc-dec with a 448-token decoder horizon has no meaningful 524k decode"}

LONG_SWA_WINDOW = 8192   # sliding-window variant for dense/vlm at long_500k


def microbatches_for(cfg, shape) -> int:
    n = cfg.param_count()
    if n > 1e11:
        return 32
    if n > 2e10:
        return 16
    if n > 3e9:
        return 4
    return 1


def needs_fsdp(cfg, m_size) -> bool:
    """fsdp costs per-microbatch weight regathers; only pay when the fp32
    param+moment state cannot fit with model-axis sharding alone."""
    return cfg.param_count() * 12 / max(m_size, 1) > 8e9


def needs_serve_fsdp(cfg, m_size) -> bool:
    """Weight-gathered serving (ZeRO-inference) when bf16 params exceed the
    HBM budget under model-axis sharding alone (llama3-405b)."""
    return cfg.param_count() * 2 / max(m_size, 1) > 10e9


def optimizer_for(cfg):
    """>=100B-param models use factored second moments (Adafactor): AdamW's
    2x fp32 moments exceed single-pod HBM at 405B (a finding of the first
    dry-run, recorded in EXPERIMENTS.md §Perf)."""
    from repro.training import optimizer as opt
    if cfg.param_count() > 1e11:
        return opt.AdafactorConfig()
    return opt.AdamWConfig()


def arch_config(arch: str, shape_name: str, variant=None):
    variant = variant or {}
    cfg = registry.config(arch)
    shape = INPUT_SHAPES[shape_name]
    note = ""
    if shape.kind == "train":
        cfg = cfg.replace(remat=True, param_dtype="float32",
                          remat_policy=("dots" if variant.get("remat_dots")
                                        else "full"))
    if shape_name == "long_500k" and cfg.family in ("dense", "vlm") \
            and cfg.sliding_window is None:
        cfg = cfg.replace(sliding_window=LONG_SWA_WINDOW,
                          name=cfg.name + "-swa8k")
        note = f"sliding-window({LONG_SWA_WINDOW}) variant for sub-quadratic long decode"
    return cfg, shape, note


def build(model, mesh, pol, shape, cfg, quantized=False, cache_int8=False):
    if shape.kind == "train":
        return steps.build_train_step(model, mesh, pol, shape,
                                      num_microbatches=microbatches_for(cfg, shape),
                                      ocfg=optimizer_for(cfg))
    if shape.kind == "prefill":
        return steps.build_prefill_step(model, mesh, pol, shape,
                                        quantized=quantized, cache_int8=cache_int8)
    return steps.build_decode_step(model, mesh, pol, shape,
                                   quantized=quantized, cache_int8=cache_int8)


def flatten_inputs(kind, inputs):
    if kind == "train":
        return (inputs["params"], inputs["opt_state"], inputs["batch"])
    if kind == "prefill":
        return (inputs["params"], inputs["tokens"], inputs["cache"], inputs["extras"])
    return (inputs["params"], inputs["tokens"], inputs["cache"], inputs["extras"])


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose=True,
            variant=None):
    variant = variant or {}
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
    cfg, shape, note = arch_config(arch, shape_name, variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = mesh_axis_sizes(mesh)
    data_ax = ("pod", "data") if multi_pod else "data"
    m_size = sizes.get("model", 1)
    fsdp = (needs_fsdp(cfg, m_size) if shape.kind == "train"
            else needs_serve_fsdp(cfg, m_size))
    expert_2d = (cfg.family == "moe"
                 and cfg.param_count() * 2 / m_size > 10e9)
    serve_2d = bool(variant.get("serve_2d")) and shape.kind != "train"
    pol = ShardingPolicy(data=data_ax, model="model", fsdp=fsdp,
                         expert_2d=expert_2d,
                         replicate_batch=serve_2d,
                         mesh_axis_sizes=sizes)
    model = build_model(cfg)
    t0 = clock.wall()
    with mesh:
        jitted, inputs = build(model, mesh, pol, shape, cfg,
                               quantized=bool(variant.get("int8_w")),
                               cache_int8=bool(variant.get("int8_kv")))
        lowered = jitted.lower(*flatten_inputs(shape.kind, inputs))
        t_lower = clock.wall() - t0
        compiled = lowered.compile()
        t_compile = clock.wall() - t0 - t_lower

    mem = hlo_analysis.memory_numbers(compiled)
    cost = hlo_analysis.cost_numbers(compiled)
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    chips = mesh.devices.size
    # PRIMARY roofline terms: analytic (XLA cost_analysis counts lax.scan
    # bodies ONCE — verified; see EXPERIMENTS.md §Dry-run). HLO numbers are
    # kept as cross-checks: raw (lower bound) and raw*trips (upper bound).
    from repro.core import analytic_cost
    import jax.numpy as _jnp
    acost = analytic_cost.step_cost(
        cfg, shape, chips=chips, fsdp=pol.fsdp,
        num_microbatches=(microbatches_for(cfg, shape)
                          if shape.kind == "train" else 1),
        data_size=sizes.get("data", 1) * sizes.get("pod", 1),
        w_bytes=(1 if variant.get("int8_w") and shape.kind != "train" else None),
        cache_elem_bytes=(1 if variant.get("int8_kv") else 2),
        weight_gather=(pol.fsdp and shape.kind != "train"
                       and not variant.get("serve_2d")))
    trips = analytic_cost.scan_trips(
        cfg, shape.kind,
        microbatches_for(cfg, shape) if shape.kind == "train" else 1)
    terms = cost_model.roofline_terms(acost.flops, acost.hbm_bytes,
                                      acost.collective_bytes, chips)
    n_tok = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * n_tok
    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "note": note, "kind": shape.kind,
        "chips": chips,
        "params": cfg.param_count(), "active_params": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": acost.flops, "hbm_bytes": acost.hbm_bytes,
        "collective_bytes": acost.collective_bytes,
        "hlo_flops_raw": cost["flops"] * chips,
        "hlo_bytes_raw": cost["bytes"] * chips,
        "hlo_collective_raw": coll.total_bytes * chips,
        "scan_trips": trips,
        "collectives": coll.summary(),
        "per_device_arg_bytes": mem["argument_size_in_bytes"],
        "per_device_temp_bytes": mem["temp_size_in_bytes"],
        "per_device_out_bytes": mem["output_size_in_bytes"],
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "model_flops": model_flops,
        "useful_flop_frac": model_flops / acost.flops if acost.flops else 0.0,
    }
    if verbose:
        print(f"== {arch} x {shape_name} (multi_pod={multi_pod}, chips={chips}) {note}")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: arg={mem['argument_size_in_bytes']/1e9:.2f}GB "
              f"temp={mem['temp_size_in_bytes']/1e9:.2f}GB "
              f"out={mem['output_size_in_bytes']/1e9:.2f}GB per device")
        print(f"   analytic (global): flops={acost.flops:.3e} "
              f"bytes={acost.hbm_bytes:.3e} coll={acost.collective_bytes:.3e}")
        print(f"   HLO cross-check (/device, scan body x1): "
              f"flops={cost['flops']:.3e} bytes={cost['bytes']:.3e} trips={trips}")
        print(f"   collectives: {coll.summary()}")
        print(f"   roofline: compute={terms.compute_s*1e3:.2f}ms "
              f"memory={terms.memory_s*1e3:.2f}ms "
              f"collective={terms.collective_s*1e3:.2f}ms -> {terms.dominant}-bound; "
              f"useful-FLOP frac={rec['useful_flop_frac']:.2f}")
    return rec


def load_results():
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_result(rec, tag=""):
    res = load_results()
    key = f"{rec['arch']}|{rec['shape']}|{'mp' if rec['multi_pod'] else 'sp'}|{tag}"
    res[key] = rec
    RESULTS.write_text(json.dumps(res, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="", help="results key suffix (perf variants)")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--int8-w", action="store_true", help="int8 serving weights")
    ap.add_argument("--int8-kv", action="store_true", help="int8 KV cache")
    ap.add_argument("--serve-2d", action="store_true",
                    help="replicate batch; shard weights+cache over both axes")
    ap.add_argument("--remat-dots", action="store_true",
                    help="remat policy: save MXU outputs instead of full recompute")
    args = ap.parse_args()
    variant = {"int8_w": args.int8_w, "int8_kv": args.int8_kv,
               "serve_2d": args.serve_2d, "remat_dots": args.remat_dots}

    archs = [a for a in registry.ARCHS if a != "llama3.2-3b"] if args.all or not args.arch \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    done = load_results() if args.skip_done else {}
    failures = []
    for arch in archs:
        for shape in shapes:
            key = f"{arch}|{shape}|{'mp' if args.multi_pod else 'sp'}|{args.tag}"
            if key in done and done[key].get("status") in ("ok", "skipped"):
                continue
            try:
                rec = run_one(arch, shape, args.multi_pod, variant=variant)
                save_result(rec, args.tag)
                if rec["status"] == "skipped":
                    print(f"== {arch} x {shape}: SKIPPED ({rec['reason']})")
            except (RuntimeError, ValueError, TypeError) as e:
                # a lowering/compile failure: record it, keep sweeping
                print(f"== {arch} x {shape}: FAILED {e}")
                traceback.print_exc()
                failures.append((arch, shape, str(e)))
                save_result({"arch": arch, "shape": shape,
                             "multi_pod": args.multi_pod, "status": "failed",
                             "error": str(e)[:2000]}, args.tag)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUNS OK")


if __name__ == "__main__":
    main()
