import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  Tests may override the count via REPRO_DRYRUN_DEVICES
# *when launching this script in a subprocess* — never in-process.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production mesh(es), prove the sharding is coherent, and capture the numbers
the roofline analysis reads.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all                     # single-pod 16x16
  python -m repro.launch.dryrun --all --multi-pod         # 2x16x16
  python -m repro.launch.dryrun --all --mesh test         # tiny CPU mesh

Outputs one JSON per combo under experiments/dryrun/.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ALIASES, get_config
from repro.launch.analytic import analytic_report
from repro.analysis.hlo import analyze
from repro.launch.mesh import make_production_mesh, make_test_mesh, num_client_rows
from repro.launch.specs import INPUT_SHAPES, input_specs
from repro.launch.steps import build_step
from repro.models import build_model

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


# §Perf hillclimb variants: named, reproducible deviations from the baseline.
# cfg: ModelConfig overrides; train: make_train_step kwargs.
VARIANTS = {
    "baseline": {},
    "afa_gram": {"train": {"afa_variant": "gram"}},
    "scan_int8": {"cfg": {"fed_mode": "scan"}, "train": {"proposal_dtype": "int8"}},
    "scan_bf16": {"cfg": {"fed_mode": "scan"}, "train": {"proposal_dtype": "bfloat16"}},
    "local8": {"train": {"local_steps": 8}, "local_steps": 8},
    "act_shard": {"cfg": {"activation_sharding": True}},
    "microbatch8": {"train": {"microbatch": 8}},
    "act_shard_mb8": {"cfg": {"activation_sharding": True}, "train": {"microbatch": 8}},
    "scan_int8_mb8": {"cfg": {"fed_mode": "scan"},
                      "train": {"proposal_dtype": "int8", "microbatch": 8}},
    "scan_int8_mb32": {"cfg": {"fed_mode": "scan"},
                       "train": {"proposal_dtype": "int8", "microbatch": 32}},
    "remat_mb32": {"train": {"microbatch": 32}},
    "fsdp_act": {"cfg": {"fsdp_activations": True}},
    "fsdp_act_mb8": {"cfg": {"fsdp_activations": True}, "train": {"microbatch": 8}},
    "scan_int8_fsdp_mb8": {"cfg": {"fed_mode": "scan", "fsdp_activations": True},
                           "train": {"proposal_dtype": "int8", "microbatch": 8}},
    "seq_par": {"cfg": {"seq_par_attention": True, "block_q": 2064}},
    "scan_int8_act_mb32": {"cfg": {"fed_mode": "scan", "activation_sharding": True},
                           "train": {"proposal_dtype": "int8", "microbatch": 32}},
    "scan_int8_fsdp_mb32": {"cfg": {"fed_mode": "scan", "fsdp_activations": True},
                            "train": {"proposal_dtype": "int8", "microbatch": 32}},
    "scan_int8_fsdp_mb16": {"cfg": {"fed_mode": "scan", "fsdp_activations": True},
                            "train": {"proposal_dtype": "int8", "microbatch": 16}},
    "afa_gram_act": {"cfg": {"activation_sharding": True}, "train": {"afa_variant": "gram"}},
}


def run_one(arch: str, shape_name: str, mesh, mesh_tag: str, out_dir: str,
            *, force: bool = False, skip_hlo: bool = False,
            variant: str = "baseline") -> dict:
    os.makedirs(out_dir, exist_ok=True)
    vtag = "" if variant == "baseline" else f"__{variant}"
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}{vtag}.json")
    if os.path.exists(fname) and not force:
        with open(fname) as f:
            return json.load(f)

    vspec = VARIANTS[variant]
    cfg = get_config(arch)
    if vspec.get("cfg"):
        cfg = cfg.with_(**vspec["cfg"])
    model = build_model(cfg)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag, "variant": variant,
        "mesh_axes": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        "status": "error",
    }
    try:
        bundle = input_specs(model, shape_name, mesh,
                             local_steps=vspec.get("local_steps"))
        rec["meta"] = bundle.meta
        if bundle.step_kind == "skip":
            rec["status"] = "skip"
            rec["skip_reason"] = bundle.skip_reason
            _dump(fname, rec)
            return rec
        step = build_step(model, bundle, mesh, **vspec.get("train", {})) \
            if bundle.step_kind == "train" else build_step(model, bundle, mesh)
        nchips = len(jax.devices()) if mesh_tag == "test" else int(
            __import__("numpy").prod([mesh.shape[a] for a in mesh.axis_names])
        )

        t0 = time.perf_counter()
        with mesh:
            lowered = jax.jit(step).lower(*bundle.args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        rec["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
        rec["cost_analysis_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        if not skip_hlo:
            t0 = time.perf_counter()
            rec["hlo"] = analyze(compiled.as_text())
            rec["hlo_analyze_s"] = round(time.perf_counter() - t0, 2)
        rec["analytic"] = analytic_report(cfg, shape_name, num_client_rows(mesh))
        rec["num_chips"] = nchips
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — each combo must report, not die
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _dump(fname, rec)
    return rec


def _dump(fname, rec):
    with open(fname, "w") as f:
        json.dump(rec, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (see repro.configs.ALIASES)")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "test"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    args = ap.parse_args()

    if args.multi_pod:
        args.mesh = "multipod"
    if args.mesh == "test":
        mesh = make_test_mesh(data=2, model=2)
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))

    archs = list(ALIASES) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]

    results = []
    for arch in archs:
        for shape in shapes:
            t0 = time.perf_counter()
            rec = run_one(arch, shape, mesh, args.mesh, args.out,
                          force=args.force, skip_hlo=args.skip_hlo,
                          variant=args.variant)
            dt = time.perf_counter() - t0
            line = f"[{rec['status']:5s}] {arch:22s} {shape:12s} {args.mesh:8s} ({dt:6.1f}s)"
            if rec["status"] == "ok":
                # memory_analysis is PER-DEVICE post-SPMD (see roofline.py)
                line += f" temp/chip={rec['memory']['temp_bytes']/2**30:.2f}GiB"
            elif rec["status"] == "skip":
                line += f" {rec['skip_reason']}"
            else:
                line += f" {rec['error'][:120]}"
            print(line, flush=True)
            results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = len(results) - n_ok - n_skip
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skip, {n_err} error ==")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
