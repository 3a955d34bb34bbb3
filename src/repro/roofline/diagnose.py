"""Hillclimb profiler: top traffic / collective contributors of a cell's
partitioned HLO, loop-multiplied. This is the 'profile' of the dry-run
methodology (no wall-clock on CPU): what to look at before forming a
hypothesis.

Usage (own process — forces 512 devices):
  PYTHONPATH=src python -m repro.roofline.diagnose --arch yi-34b \
      --shape decode_32k [--multipod] [--override unroll=True] [--top 20]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import re

import jax

from repro.roofline import hlo_graph as H


def walk_items(hlo: str):
    comps = H.parse_computations(hlo)
    m = re.search(r"ENTRY\s+%?([\w.\-_]+)", hlo)
    entry = m.group(1) if m else list(comps)[-1]
    traffic, colls = [], []

    def walk(comp_name, mult, stack=()):
        comp = comps.get(comp_name)
        if comp is None or comp_name in stack:
            return
        for inst in comp.instrs:
            op = inst.op
            base = op.replace("-start", "")
            if base in H.COLLECTIVES:
                rb = H._shape_elems_bytes(inst.type_str)
                g = H._group_size(inst.rest, 1)
                colls.append((mult * H._wire_bytes(base, rb, g), mult, base,
                              inst.name, comp_name))
            if op == "while":
                mb = re.search(r"body=%?([\w.\-_]+)", inst.rest)
                mc = re.search(r"condition=%?([\w.\-_]+)", inst.rest)
                trips = H._trip_count(comps, mc.group(1)) if mc else 1
                if mb:
                    walk(mb.group(1), mult * trips, stack + (comp_name,))
                continue
            if op in H._SKIP_TRAFFIC:
                continue
            if op == "fusion" and inst.called:
                sub = comps.get(inst.called[0])
                dus_out = H._dus_root_result_bytes(sub) if sub else None
                t = dus_out if dus_out is not None else \
                    H._shape_elems_bytes(inst.type_str)
                ops_ = H._OPERAND.findall(inst.rest.split(" calls=")[0])
                sliced = H._sliced_params(sub) if sub else {}
                for idx, opnd in enumerate(ops_):
                    if opnd in comp.types:
                        t += sliced.get(idx,
                                        H._shape_elems_bytes(comp.types[opnd]))
                traffic.append((mult * t, mult, op, inst.name, comp_name))
            elif op in ("dynamic-slice", "slice", "gather"):
                traffic.append((mult * 2 * H._shape_elems_bytes(inst.type_str),
                                mult, op, inst.name, comp_name))
            elif op == "dynamic-update-slice":
                ops_ = H._OPERAND.findall(inst.rest)
                upd = (H._shape_elems_bytes(comp.types[ops_[1]])
                       if len(ops_) > 1 and ops_[1] in comp.types else 0)
                traffic.append((mult * 2 * upd, mult, op, inst.name, comp_name))
            else:
                t = H._shape_elems_bytes(inst.type_str)
                for opnd in H._OPERAND.findall(inst.rest):
                    if opnd in comp.types:
                        t += H._shape_elems_bytes(comp.types[opnd])
                traffic.append((mult * t, mult, op, inst.name, comp_name))
            if op in ("call", "conditional") and inst.called:
                for c in inst.called:
                    walk(c, mult, stack + (comp_name,))

    walk(entry, 1.0)
    return traffic, colls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--override", action="append", default=[])
    args = ap.parse_args()

    from repro.configs.base import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import make_cell

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=")
        overrides[k] = eval(v)  # noqa: S307 - CLI convenience
    cfg = get_config(args.arch)
    mesh = make_production_mesh(multi_pod=args.multipod)
    cell = make_cell(cfg, SHAPES[args.shape], mesh, **overrides)
    with jax.set_mesh(mesh):
        hlo = cell.lower().compile().as_text()
    traffic, colls = walk_items(hlo)
    traffic.sort(reverse=True)
    colls.sort(reverse=True)
    tt = sum(t[0] for t in traffic)
    tc = sum(c[0] for c in colls)
    print(f"== traffic {tt / 1e9:.1f} GB/dev (mem term "
          f"{tt / H.__dict__.get('HBM', 819e9):.3f}s) — top {args.top} ==")
    for t, mult, op, name, comp in traffic[:args.top]:
        print(f"  {t / 1e9:9.2f} GB x{mult:6.0f} {op:22s} {name[:48]} "
              f"[{comp[:28]}]")
    print(f"== collectives {tc / 1e9:.1f} GB wire/dev "
          f"({tc / 50e9:.3f}s) — top {args.top} ==")
    for t, mult, op, name, comp in colls[:args.top]:
        print(f"  {t / 1e9:9.2f} GB x{mult:6.0f} {op:22s} {name[:48]} "
              f"[{comp[:28]}]")


if __name__ == "__main__":
    main()
