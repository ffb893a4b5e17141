import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: JAX locks the device count on first
initialization, and the dry-run needs 512 placeholder host devices to build
the production meshes. Smoke tests and benchmarks do NOT import this module
(they see 1 device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch command-r-35b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, ALL_SHAPES
from repro.distributed.roofline import roofline_from
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import build_cell, skip_reason

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../dryrun_artifacts")


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides: dict | None = None) -> dict:
    cfg = REGISTRY[arch]
    base = {"compute_dtype": jnp.bfloat16, "remat": "dots"}
    base.update(overrides or {})
    cfg = dataclasses.replace(cfg, **base)
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size

    reason = skip_reason(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_chips": n_chips, "status": None,
    }
    if reason:
        record["status"] = "skipped"
        record["skip_reason"] = reason
        return record

    t0 = time.time()
    cell = build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(mem)     # proves it fits
    print({k: cost.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    from repro.distributed.hlo_analysis import analyze_hlo
    totals = analyze_hlo(hlo)
    coll = type("C", (), {"link_bytes": totals.coll_bytes,
                          "per_op_bytes": totals.coll_per_op,
                          "n_ops": {}})
    roof = roofline_from(cost, hlo, n_chips=n_chips,
                         model_flops=cell.model_flops)

    record.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "collectives": {
            "link_bytes": coll.link_bytes,
            "per_op": coll.per_op_bytes,
            "n_ops": coll.n_ops,
        },
        "roofline": roof.row(),
    })
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--tag", default="")
    ap.add_argument("--micro", type=int, default=None,
                    help="override gradient-accumulation factor")
    ap.add_argument("--remat", default=None, choices=("none", "dots", "full"))
    ap.add_argument("--attn-q-chunk", type=int, default=None)
    ap.add_argument("--attn-k-chunk", type=int, default=None)
    ap.add_argument("--scan-chunk", type=int, default=None)
    ap.add_argument("--scores-bf16", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    overrides = {}
    if args.micro is not None:
        overrides["microbatches"] = args.micro
    if args.remat is not None:
        overrides["remat"] = args.remat
    if args.attn_q_chunk is not None:
        overrides["attn_q_chunk"] = args.attn_q_chunk
    if args.attn_k_chunk is not None:
        overrides["attn_k_chunk"] = args.attn_k_chunk
    if args.scan_chunk is not None:
        overrides["scan_chunk"] = args.scan_chunk
    if args.scores_bf16:
        overrides["attn_scores_f32"] = False

    os.makedirs(args.out, exist_ok=True)
    archs = sorted(REGISTRY) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                name = f"{arch}_{shape}_{mesh_kind}{args.tag}"
                path = os.path.join(args.out, name + ".json")
                try:
                    rec = run_cell(arch, shape, mesh_kind, args.out,
                                   overrides=overrides)
                except Exception as e:  # a failure here is a bug in the system
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "status": "FAILED", "error": repr(e),
                        "traceback": traceback.format_exc()[-4000:],
                    }
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[{rec['status']:>7s}] {name} "
                      + (f"compile={rec.get('compile_s')}s "
                         f"mem={rec.get('memory', {}).get('peak_per_device_gb')}GB "
                         f"bound={rec.get('roofline', {}).get('bound')}"
                         if rec["status"] == "ok" else
                         rec.get("skip_reason", rec.get("error", ""))[:120]))
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
