"""Three-term roofline from compiled dry-run artifacts (no hardware).

    compute term    = HLO_FLOPs / (chips × 197 TFLOP/s)
    memory term     = HLO_bytes / (chips × 819 GB/s)
    collective term = collective_bytes / (chips × 50 GB/s per ICI link)

``compiled.cost_analysis()`` supplies FLOPs / bytes-accessed.  Collective
bytes are parsed from the HLO text: we sum *operand* sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
with a ring-algorithm wire factor (all-reduce moves ≈2× its operand bytes;
the others ≈1×).  cost_analysis numbers on a partitioned module are
per-device, so terms divide by per-chip peaks directly.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.launch.mesh import PRODUCTION_CHIP, chip_peaks

_PEAKS = chip_peaks(PRODUCTION_CHIP)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

# result shapes like `bf16[16,128,1024]{2,1,0}` or tuples `(f32[8], f32[8])`
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# `%name = <shape(s)> <collective-kind>(...operands...)`
_OP_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def _wire_bytes(kind: str, result_bytes: int, group: int) -> float:
    """Per-device wire traffic for a ring implementation.

    all-gather result = gathered tensor; each device sends its (1/g) shard
    (g−1) times ⇒ wire ≈ result·(g−1)/g.
    all-reduce (≡ reduce-scatter + all-gather) ⇒ ≈ 2·result·(g−1)/g.
    reduce-scatter result = the shard; input = result·g ⇒ ≈ result·(g−1).
    all-to-all: each device keeps 1/g, sends the rest ⇒ ≈ result·(g−1)/g.
    collective-permute: one send per device ⇒ result.
    """
    g = max(2, group)
    f = (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * result_bytes * f
    if kind == "reduce-scatter":
        return result_bytes * (g - 1)
    if kind == "collective-permute":
        return float(result_bytes)
    return result_bytes * f


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes per collective kind, parsed from HLO text.

    NOTE: while-loop (lax.scan) bodies appear once in the text, so collectives
    inside scans are counted once — the dry-run probes therefore lower with
    RunConfig.unroll=True so every structural loop is unrolled.
    """
    totals: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    # pass 1: group sizes for async starts (the -done line lacks the attr)
    start_groups: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_LINE_RE.search(line)
        if m and m.group(3) == "-start":
            name = line.split("=", 1)[0].strip().lstrip("%")
            gm = _GROUPS_RE.search(line)
            start_groups[name] = int(gm.group(2)) if gm else 2
    # pass 2: count sync ops and -done results
    for line in hlo_text.splitlines():
        m = _OP_LINE_RE.search(line)
        if not m:
            continue
        shape_str, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-start":
            continue   # counted at the matching -done (clean result shape)
        if startdone == "-done":
            om = re.search(r"\(%?([\w.\-]+)", line[m.end() - 1:])
            group = start_groups.get(om.group(1), 2) if om else 2
        else:
            gm = _GROUPS_RE.search(line)
            group = int(gm.group(2)) if gm else 2
        totals[kind] += _wire_bytes(kind, _shape_bytes(shape_str), group)
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    coll_bytes: float           # per device (wire)
    model_flops: float          # 6·N·D (or 6·N_active·D) total, fwd+bwd
    coll_breakdown: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / _PEAKS["bf16_flops"]

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / _PEAKS["hbm_bytes_per_s"]

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / _PEAKS["ici_bytes_per_s"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips) — remat/redundancy waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "hlo_flops_per_dev": self.hlo_flops,
            "hlo_bytes_per_dev": self.hlo_bytes,
            "coll_bytes_per_dev": self.coll_bytes,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
        }


RING_DTYPE_BYTES = {"fp32": 4, "bf16": 2}

# optimizer state slots carried next to the ring (fp32 each): sgd none,
# momentum/adagrad one (velocity / accumulator), adamw two (m, v)
_OPT_STATE_SLOTS = {"sgd": 0, "momentum": 1, "adagrad": 1, "adamw": 2}


def ring_bytes(K: int, D: int, ring_dtype: str = "fp32",
               optimizer: str = "momentum", donated: bool = True) -> Dict:
    """Device-resident bytes of the replay engine's hot-loop carry
    (DESIGN.md §12): the (K, D) weight ring in ``ring_dtype``, the fp32
    optimizer state, and — with a compressed (bf16) ring — the fp32
    error-feedback residue of the latest row.  ``donated=False`` models
    the pre-megakernel scan, whose undonated carry is double-buffered
    across dispatches (2× every term).  This is the feasibility limit the
    what-if replay runs against: max feasible D ≈ HBM / bytes_per_param.
    """
    per = RING_DTYPE_BYTES.get(ring_dtype)
    if per is None:
        raise ValueError(f"unknown ring_dtype {ring_dtype!r}; expected one "
                         f"of {sorted(RING_DTYPE_BYTES)}")
    slots = _OPT_STATE_SLOTS.get(optimizer, 1)
    ring = K * D * per
    state = slots * D * 4
    residue = D * 4 if ring_dtype == "bf16" else 0
    mult = 1 if donated else 2
    total = (ring + state + residue) * mult
    return {
        "ring_bytes": ring * mult,
        "state_bytes": state * mult,
        "residue_bytes": residue * mult,
        "total_bytes": total,
        "bytes_per_param": total / D if D else 0.0,
    }


def normalize_cost_analysis(cost) -> Dict:
    """``compiled.cost_analysis()`` across jaxlib versions: older releases
    return a per-partition list of dicts (one entry on a single module),
    newer ones return the dict directly.  Normalize to one flat dict so
    every consumer can ``cost.get("flops")`` without version checks."""
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        merged: Dict = {}
        for entry in cost:
            for k, v in dict(entry).items():
                merged[k] = merged.get(k, 0.0) + v
        return merged
    return dict(cost)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D for training (fwd+bwd), 2·N·D for inference,
    with N = active params (MoE) and D = tokens processed."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def analyse(arch: str, shape_name: str, mesh_name: str, chips: int,
            cost: Dict, hlo_text: str, mf: float) -> Roofline:
    coll = collective_bytes(hlo_text)
    cost = normalize_cost_analysis(cost)
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=coll["total"],
        model_flops=mf,
        coll_breakdown=coll,
    )
