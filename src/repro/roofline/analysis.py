"""Roofline extraction from compiled dry-run artifacts.

Three terms (seconds, per device):
  compute    = HLO_FLOPs_per_device / PEAK_FLOPS
  memory     = HLO_bytes_per_device / HBM_BW
  collective = sum over collective ops of ring-model bytes / ICI_BW

cost_analysis() on an SPMD-partitioned executable reports PER-DEVICE flops
and bytes (verified empirically). Collective bytes are parsed from the
partitioned HLO text (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, sync and -start async forms).
"""
from __future__ import annotations

import re
from dataclasses import dataclass

# --- TPU v5e-class hardware constants (per chip) ---
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s+(?P<rtype>\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _bytes_of_type(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, default_group: int = 1):
    """Returns {op: {count, result_bytes, wire_bytes}} per device (ring model)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        rbytes = _bytes_of_type(m.group("rtype"))
        g = _group_size(line, default_group)
        if g <= 1:
            wire = 0
        elif op == "all-gather":
            wire = rbytes * (g - 1) // g
        elif op == "reduce-scatter":
            wire = rbytes * (g - 1)
        elif op == "all-reduce":
            wire = 2 * rbytes * (g - 1) // g
        elif op == "all-to-all":
            wire = rbytes * (g - 1) // g
        else:  # collective-permute
            wire = rbytes
        rec = out.setdefault(op, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        rec["count"] += 1
        rec["result_bytes"] += rbytes
        rec["wire_bytes"] += wire
    return out


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float

    @property
    def compute_s(self):
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self):
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self):
        return self.wire_bytes_per_device / ICI_BW

    @property
    def dominant(self):
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self):
        return max(self.compute_s, self.memory_s, self.collective_s)

    def asdict(self):
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def from_compiled(compiled) -> dict:
    """Extract per-device roofline + memory record from a compiled executable.

    Roofline terms use LOOP-CORRECTED counts (repro.roofline.hlo_graph):
    cost_analysis() counts while bodies once, so scanned layers / microbatch
    loops would otherwise be undercounted by their trip counts. The raw
    cost_analysis numbers are recorded alongside for reference.
    """
    from repro.roofline import hlo_graph

    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    la = hlo_graph.analyze(hlo)
    rl = Roofline(la.dot_flops, la.traffic_bytes, la.wire_bytes)
    mem = compiled.memory_analysis()
    return {
        "roofline": rl.asdict(),
        "collectives": la.collectives,
        "while_trips": la.while_trips,
        "raw_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        },
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_bytes": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
        },
    }


def model_flops(cfg, shape, n_active_params: int) -> float:
    """Standard 6*N*D (active params) model-FLOPs estimate for the cell."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active_params * tokens
    return 2.0 * n_active_params * shape.global_batch  # decode: one token
