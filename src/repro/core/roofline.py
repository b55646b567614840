"""Three-term roofline analysis from compiled XLA artifacts (DESIGN.md §7).

The roofline terms are derived structurally from a compiled module, with
the peaks of the device it targets (``PEAKS``, keyed by the
``device_kind`` JAX reports):

    compute    = HLO_FLOPs            / (chips * peak FLOP/s)
    memory     = HLO_bytes_accessed   / (chips * HBM bytes/s)
    collective = collective_bytes     / (chips * ICI bytes/s per link)

``compiled.cost_analysis()`` on an SPMD-partitioned module reports
*per-device* flops/bytes (verified empirically: a 512-way sharded matmul
reports global/512), so the per-chip terms divide by PEAK directly.
Collective bytes are parsed from the optimized HLO text: we sum the result
shapes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction (all-reduce counted twice: ring reduce =
2.(n-1)/n ~ 2x the payload).
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind, with their source."""

    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s per link (2-D torus: the dominant link)
    source: str


#: ``device_kind`` as JAX reports it -> published peaks.  A device that
#: is not listed has no roofline: ``peaks`` raises, it never defaults.
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=394e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interchip interconnect over 4 links
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e' (system "
               "architecture): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB "
               "HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}

#: The chip the dry run and the paper-scale tables model.
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; KeyError for a device with none listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add "
            "them to core/roofline.PEAKS with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9]+m[0-9]+(?:fn)?)?)\[([0-9,]*)\]")
_COLL_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-op bytes from optimized HLO (per-device shapes).

    Counts the *result* shapes of each collective instruction.  Start/done
    pairs (async collectives) are counted once, on the -start op; all-reduce
    weighted 2x (ring all-reduce moves ~2 payloads per device).
    """
    out: dict[str, int] = {op: 0 for op in _COLL_OPS}
    counts: dict[str, int] = {op: 0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        m = re.match(r"\s*((?:\([^)]*\))|(?:[a-z0-9_\[\],{}: ]+?))\s+"
                     r"([a-z0-9-]+)\(", rhs)
        if not m:
            continue
        op = m.group(2)
        base = op.removesuffix("-start")
        if base not in _COLL_OPS or op.endswith("-done"):
            continue
        restype = m.group(1)
        nbytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(restype)
        )
        weight = 2 if base == "all-reduce" else 1
        out[base] += nbytes * weight
        counts[base] += 1
    out["_counts"] = counts  # type: ignore[assignment]
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float            # 6*N*D (global, useful)
    useful_ratio: float           # model_flops / (flops_per_chip*chips)
    coll_breakdown: dict

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def roofline_fraction(self) -> float:
        """How close the dominant term says we are to the compute roofline:
        T_compute / T_bound (1.0 = compute-bound at peak)."""
        if self.t_bound == 0:
            return 0.0
        return self.t_compute / self.t_bound

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "flops/chip": f"{self.flops_per_chip:.3e}",
            "bytes/chip": f"{self.bytes_per_chip:.3e}",
            "coll_bytes/chip": f"{self.coll_bytes_per_chip:.3e}",
            "t_comp_s": f"{self.t_compute:.4e}",
            "t_mem_s": f"{self.t_memory:.4e}",
            "t_coll_s": f"{self.t_collective:.4e}",
            "bound": self.bottleneck,
            "useful": f"{self.useful_ratio:.3f}",
            "roofline_frac": f"{self.roofline_fraction():.3f}",
        }


def analyze(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cost: dict,
    hlo_text: str,
    model_flops: float,
    device_kind: str,
) -> Roofline:
    pk = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    coll_total = float(sum(v for k, v in coll.items() if k != "_counts"))

    t_comp = flops / pk.bf16_flops
    t_mem = nbytes / pk.hbm_bw
    t_coll = coll_total / pk.ici_bw
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)  # type: ignore[arg-type]
    useful = model_flops / (flops * chips) if flops > 0 else 0.0
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        flops_per_chip=flops,
        bytes_per_chip=nbytes,
        coll_bytes_per_chip=coll_total,
        t_compute=t_comp,
        t_memory=t_mem,
        t_collective=t_coll,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=useful,
        coll_breakdown=coll,
    )


def collective_time_s(nbytes: float, link_gbps: float) -> float:
    """Wire time for ``nbytes`` over a ``link_gbps`` GB/s interconnect —
    the outer-level term of the hierarchical combined cost model (the
    inner level keeps its PLIO model; this prices the inter-chip link)."""
    if nbytes <= 0:
        return 0.0
    return float(nbytes) / (link_gbps * 1e9)


def format_table(rows: list[Roofline]) -> str:
    if not rows:
        return "(empty)"
    cols = list(rows[0].row().keys())
    data = [list(r.row().values()) for r in rows]
    widths = [
        max(len(c), *(len(row[i]) if isinstance(row[i], str) else len(str(row[i]))
                      for row in data))
        for i, c in enumerate(cols)
    ]
    def fmt(vals):
        return " | ".join(str(v).ljust(w) for v, w in zip(vals, widths))
    lines = [fmt(cols), "-|-".join("-" * w for w in widths)]
    lines += [fmt(row) for row in data]
    return "\n".join(lines)
