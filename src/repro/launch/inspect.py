import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Cell inspector: rank the collective / memory hot spots of one dry-run cell.

The §Perf loop's "profile" (DESIGN.md: the profile is the lowered IR +
cost_analysis, not a wall-clock trace):

    PYTHONPATH=src python -m repro.launch.inspect --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod] [--top 25] [--kind collective|memory]
"""

import argparse
import collections

import jax

from repro.configs import registry
from repro.core.costmodel import CostModel
from repro.core.hlo import parse_hlo_module, _CostVisitor, COLLECTIVE_OPS
from repro.launch.mesh import make_production_mesh
from repro.launch.cell import build_cell
from repro.launch.dryrun import mesh_topology, DEVICES_PER_POD
from repro.sharding import ShardingRules


def rank_cell(arch: str, shape_name: str, multi_pod: bool = False,
              kind: str = "collective", top: int = 25, layout: str = "v2"):
    cfg = registry.get_config(arch)
    shape = registry.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cost = CostModel(topo=mesh_topology(multi_pod))
    with jax.set_mesh(mesh):
        cell = build_cell(cfg, shape, mesh,
                          ShardingRules(layout=layout))
        compiled = cell.lower().compile()
    module = parse_hlo_module(compiled.as_text())
    vis = _CostVisitor(module, cost, DEVICES_PER_POD)
    rows = []

    def walk(comp, mult, depth=0):
        c = module.computations.get(comp)
        if c is None or depth > 24:
            return
        types = {i.name: i.type_str for i in c.instrs}
        for i in c.instrs:
            if i.opcode == "while":
                n = module.trip_count(i) or 1
                for b in i.called():
                    walk(b, mult * n, depth + 1)
                continue
            if i.opcode in ("call", "async-start"):
                for b in i.called():
                    walk(b, mult, depth + 1)
                continue
            if i.opcode == "conditional":
                br = i.branches() or i.called()
                if br:
                    walk(br[0], mult, depth + 1)
                continue
            d = vis.classify(i, types)
            if d is None:
                continue
            is_coll = i.opcode.replace("-start", "") in COLLECTIVE_OPS
            if kind == "collective" and not is_coll:
                continue
            if kind == "memory" and is_coll:
                continue
            metric = d.get("comm_bytes", 0.0) if kind == "collective" \
                else d["bytes"]
            rows.append((mult * metric, i.opcode, mult,
                         (i.op_name or i.name)[:110]))

    walk(module.entry, 1.0)
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"{arch} x {shape_name} x "
          f"{'multi' if multi_pod else 'single'} [{kind}] "
          f"total={total/1e9:.2f} GB/device")
    agg = collections.Counter()
    for b, op, m, name in rows:
        agg[op] += b
    print({k: f"{v/1e9:.2f}GB" for k, v in agg.most_common()})
    for b, op, m, name in rows[:top]:
        print(f"{b/1e6:10.1f}MB x{m:5.0f} {op:20s} {name}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--kind", default="collective",
                    choices=["collective", "memory"])
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--layout", default="v2")
    args = ap.parse_args()
    rank_cell(args.arch, args.shape, args.multi_pod, args.kind, args.top,
              args.layout)


if __name__ == "__main__":
    main()
