"""Three-term roofline analysis from compiled dry-run artifacts.

Per-chip peaks come from :data:`PEAKS`, one table keyed by
``jax.devices()[0].device_kind``; a device that is not in it is an error.

``cost_analysis()`` on the post-SPMD module is *per chip*; so
  compute  = flops / peak bf16 FLOP/s
  memory   = bytes_accessed / HBM bytes/s
  collective = effective wire bytes per chip / ICI bytes/s per link
equivalently HLO_global/(chips·peak) as in the assignment formulas.

Collective bytes come from parsing the post-SPMD HLO: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute op's
operand size (derived from the printed result shape and replica-group size), scaled
by the ring-traffic factor of the op kind.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

# Published per-chip peaks, keyed by ``device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of ``device_kind``; unknown devices raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-gather.3 = f32[64,64]{0,1} all-gather(%x), ... replica_groups={{0,1},..}
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> List[Dict]:
    """Per-collective: kind, result bytes (local), group size, wire bytes/chip."""
    out = []
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _OP_RE.search(line)
        if not m:
            continue
        tuple_part, dtype, dims, kind = m.group(1), m.group(2), m.group(3), m.group(4)
        if tuple_part:                       # tuple result: sum components
            rbytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(tuple_part))
        else:
            rbytes = _shape_bytes(dtype, dims)
        g = 1
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mg2 = _GROUPS_IOTA_RE.search(line)
            if mg2:
                g = int(mg2.group(2))
        # effective wire bytes per chip (ring algorithms)
        if kind == "all-gather":
            wire = rbytes * (g - 1) / max(g, 1)
            operand = rbytes                      # gathered result
        elif kind == "all-reduce":
            wire = 2 * rbytes * (g - 1) / max(g, 1)
            operand = rbytes
        elif kind == "reduce-scatter":
            operand = rbytes * g                  # input is g× the output
            wire = rbytes * (g - 1)               # (g-1)/g of the input
        elif kind == "all-to-all":
            operand = rbytes
            wire = rbytes * (g - 1) / max(g, 1)
        else:                                     # collective-permute
            operand = rbytes
            wire = rbytes
        out.append({"kind": kind, "result_bytes": rbytes, "group": g,
                    "operand_bytes": operand, "wire_bytes": wire})
    return out


def summarize_collectives(hlo_text: str) -> Dict:
    """Aggregate :func:`parse_collectives` into the bench-gated summary.

    Returns ``{"collective_count", "operand_bytes", "wire_bytes",
    "counts_by_kind", "bytes_by_kind"}`` — per-chip totals over every
    collective in the (post-SPMD) HLO text.  ``operand_bytes`` is the sum of
    each collective's operand size, the quantity the distributed-op traffic
    closed forms (``repro.analysis.collectives.modeled_dist_traffic``) model
    and ``benchmarks/run.py dist`` gates as ``bytes_measured``.
    """
    colls = parse_collectives(hlo_text)
    counts: Dict[str, int] = {}
    bby: Dict[str, float] = {}
    for c in colls:
        counts[c["kind"]] = counts.get(c["kind"], 0) + 1
        bby[c["kind"]] = bby.get(c["kind"], 0.0) + c["operand_bytes"]
    return {
        "collective_count": len(colls),
        "operand_bytes": float(sum(c["operand_bytes"] for c in colls)),
        "wire_bytes": float(sum(c["wire_bytes"] for c in colls)),
        "counts_by_kind": counts,
        "bytes_by_kind": bby,
    }


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float      # operand bytes per chip
    wire_bytes: float            # effective ring-traffic bytes per chip
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    n_collectives: int
    by_kind: Dict[str, float]

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, *, device_kind: str, chips: int = 1) -> Roofline:
    """Roofline terms of ``compiled`` at the peaks of ``device_kind``."""
    peak = peaks(device_kind)
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    cbytes = sum(c["operand_bytes"] for c in colls)
    wire = sum(c["wire_bytes"] for c in colls)
    by_kind: Dict[str, float] = {}
    for c in colls:
        by_kind[c["kind"]] = by_kind.get(c["kind"], 0.0) + c["wire_bytes"]
    terms = {
        "compute": flops / peak["bf16_flops"],
        "memory": hbm / peak["hbm_bw"],
        "collective": wire / peak["ici_bw"],
    }
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        flops=flops, hbm_bytes=hbm, collective_bytes=cbytes, wire_bytes=wire,
        compute_s=terms["compute"], memory_s=terms["memory"],
        collective_s=terms["collective"], bottleneck=bottleneck,
        n_collectives=len(colls), by_kind=by_kind,
    )


def model_flops(n_params: int, tokens: int, kind: str,
                n_active: Optional[int] = None) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N_active·D for inference decode/prefill."""
    n = n_active if n_active is not None else n_params
    if kind == "train":
        return 6.0 * n_params * tokens if n_active is None else 6.0 * n * tokens
    return 2.0 * n * tokens
