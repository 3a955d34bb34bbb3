import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

MUST be run as its own process (the device-count flag is set before any jax
import). Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shapes_for
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_cell
from repro.models import model_api as MA
from repro.roofline import analysis as RA

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir=OUT_DIR,
             overrides=None, tag="") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multipod" if multi_pod else "pod"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        kw = dict(overrides or {})
        cell = make_cell(cfg, shape, mesh, **kw)
        with jax.set_mesh(mesh):
            lowered = cell.lower()
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            print(compiled.memory_analysis())
            cost = compiled.cost_analysis()
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed", "transcendentals")})
            rec.update(RA.from_compiled(compiled))
            rec["n_devices"] = mesh.size
            n_active = MA.active_param_count(cfg)
            rec["n_params"] = MA.param_count(cfg)
            rec["n_active_params"] = n_active
            rec["model_flops_total"] = RA.model_flops(cfg, shape, n_active)
            rec["model_flops_per_device"] = rec["model_flops_total"] / mesh.size
            hf = rec["roofline"]["flops_per_device"]
            rec["useful_flops_ratio"] = (
                rec["model_flops_per_device"] / hf if hf else 0.0)
            rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 - record the failure, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=20)
    rec["total_s"] = round(time.time() - t0, 2)
    d = pathlib.Path(out_dir) / mesh_name
    d.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    (d / f"{arch}__{shape_name}{suffix}.json").write_text(
        json.dumps(rec, indent=1, default=str))
    status = rec["status"]
    print(f"[{mesh_name}] {arch} x {shape_name}{suffix}: {status} "
          f"({rec['total_s']}s)")
    if status == "error":
        print(rec["error"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch == "all") else [args.arch]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]
    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in shapes_for(cfg)]
                  if (args.all or args.shape == "all") else [args.shape])
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "multipod" if mp else "pod"
                fp = (pathlib.Path(args.out) / mesh_name
                      / f"{arch}__{shape_name}.json")
                if args.skip_existing and fp.exists():
                    if json.loads(fp.read_text()).get("status") == "ok":
                        continue
                rec = run_cell(arch, shape_name, mp, out_dir=args.out)
                failures += rec["status"] != "ok"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
