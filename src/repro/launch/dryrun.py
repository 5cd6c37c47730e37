import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.  Everything
is ShapeDtypeStruct-based: no tensor is ever allocated.

Per cell, this driver records:
  * ``compiled.memory_analysis()``  — bytes/device (proves it fits / honest OOM)
  * ``compiled.cost_analysis()``    — XLA FLOPs/bytes
  * trip-count-aware FLOPs/bytes/collective bytes from the parsed HLO
  * the three §Roofline terms + dominant bound + useful-compute ratio

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh both --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import registry
from repro.core.costmodel import CostModel, MeshTopology
from repro.core.hlo import parse_hlo_module, aggregate_costs
from repro.core.roofline import roofline_report, format_row
from repro.launch.mesh import make_production_mesh
from repro.launch.cell import build_cell
from repro.models.model import active_params
from repro.sharding import ShardingRules

DEVICES_PER_POD = 256


def mesh_topology(multi_pod: bool) -> MeshTopology:
    return (MeshTopology.multi_pod(2, 16, 16) if multi_pod
            else MeshTopology.single_pod(16, 16))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, rules: ShardingRules = None,
             cfg_override=None, tag: str = "") -> dict:
    cfg = cfg_override or registry.get_config(arch)
    shape = registry.SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    ok, reason = registry.runnable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": reason}
        _emit(rec, out_dir, tag)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    topo = mesh_topology(multi_pod)
    cost = CostModel(topo=topo)
    try:
        with jax.set_mesh(mesh):
            cell = build_cell(cfg, shape, mesh, rules)
            lowered = cell.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            xla_cost = compiled.cost_analysis()
            module = parse_hlo_module(compiled.as_text())
            agg = aggregate_costs(module, cost,
                                  devices_per_pod=DEVICES_PER_POD)
    except Exception as e:  # a failure here is a bug in the system
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "FAILED", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        _emit(rec, out_dir, tag)
        return rec

    chips = 512 if multi_pod else 256
    rep = roofline_report(
        agg, chips=chips, kind=shape.kind,
        n_active_params=active_params(cfg), seq_len=shape.seq_len,
        global_batch=shape.global_batch, xla_cost=xla_cost,
        memory_stats=mem)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok", "kind": shape.kind,
           "seq_len": shape.seq_len, "global_batch": shape.global_batch,
           "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
           "roofline": rep}
    _emit(rec, out_dir, tag)
    return rec


def _emit(rec: dict, out_dir: Optional[str], tag: str = "") -> None:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        sfx = f"__{tag}" if tag else ""
        path = os.path.join(
            out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{sfx}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    if rec["status"] == "ok":
        print(format_row(rec["arch"], rec["shape"], rec["mesh"],
                         rec["roofline"]), flush=True)
        ma = rec["roofline"]
        print(f"    bytes/dev: args={ma.get('mem_argument_size_in_bytes', 0)/1e9:.2f}GB "
              f"temp={ma.get('mem_temp_size_in_bytes', 0)/1e9:.2f}GB "
              f"fits_hbm={ma.get('fits_hbm')} "
              f"lower={rec['lower_s']}s compile={rec['compile_s']}s",
              flush=True)
    else:
        print(f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:6s} "
              f"{rec['status']}: {rec.get('reason') or rec.get('error')}",
              flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-skipped", action="store_true")
    args = ap.parse_args()

    archs = registry.list_archs() if args.arch in ("all", "") \
        else args.arch.split(",")
    shapes = list(registry.SHAPES) if args.shape in ("all", "") \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, args.out)
                if rec["status"] == "FAILED":
                    n_fail += 1
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")
    print("dry-run complete: all cells lowered+compiled.")


if __name__ == "__main__":
    main()
