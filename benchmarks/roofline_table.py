"""Roofline tables: registry-driven structural bounds + dry-run artifacts.

Three sections (all emitted by ``run``, the ``--only roofline`` driver
hook):

1. **Registry bounds** (``registry_rows``): one ``predict_bounds`` row per
   bench case of *every registered KernelSpec* — the case list IS the
   registry (``repro/kernels/registry.py``), so a newly registered
   recurrence shows up here with zero edits (closes the ROADMAP
   "registry-driven roofline" item).  Columns are documented in
   ``docs/architecture.md`` §Roofline-table columns.

2. **Fused-chain bytes** (``chain_rows``): one row per fused
   producer→consumer chain case (the same cases the ``--ci`` bench gate
   times), comparing predicted HBM bytes of the single fused launch
   against two standalone stage launches.  The delta is exactly
   ``FusedPlan.predicted_bytes_saved`` — the intermediate's write+read
   at the accumulate dtype, the bytes the fusion keeps shard-resident
   (see ``docs/fusion.md``).

3. **Dry-run table** (``load``/``dryrun_rows``): the EXPERIMENTS.md
   §Roofline table built from ``results/dryrun/*.json`` artifacts written
   by ``repro.launch.dryrun`` (compiled-HLO rooflines of the model stack,
   not structural predictions).

    PYTHONPATH=src python benchmarks/roofline_table.py [--registry-only]
"""

from __future__ import annotations

import glob
import json
import os

from repro.core import AIE_TARGET
from repro.core.mapper import Target, best_plan, predict_bounds
from repro.core import roofline as RL
from repro.kernels import registry

V5E = RL.peaks(RL.V5E)  # the dry run models a v5e pod

CHIPS = {"16x16": 256, "2x16x16": 512}


# ---------------------------------------------------------------------------
# section 1: registry-driven structural bounds (one row per spec bench case)
# ---------------------------------------------------------------------------

def registry_rows(target: Target = AIE_TARGET) -> list[dict]:
    """``predict_bounds`` for every (spec, bench case) in the registry."""
    rows: list[dict] = []
    for spec in registry.specs():
        cases = spec.bench_cases or (("float32", spec.smoke_args),)
        for dtype, args in cases:
            rec = spec.builder(*args, dtype)
            plan = best_plan(rec, target)
            bounds = predict_bounds(rec, plan.partition, target)
            arr = "x".join(str(t) for t in plan.partition.array_tiles)
            if plan.partition.thread_factor > 1:
                arr += f"*{plan.partition.thread_factor}"
            binding = min(bounds, key=lambda k: bounds[k])
            rows.append({
                "bench": spec.name,
                "dtype": dtype,
                "array": arr,
                "util": plan.predicted_utilization,
                "compute": bounds["compute"],
                "array_level": bounds["array_level"],
                "end_to_end": bounds["end_to_end"],
                "binding": binding,
                "feasible": plan.feasible,
            })
    return rows


def format_registry_table(rows: list[dict]) -> str:
    head = (f"| {'bench':12s} | {'dtype':7s} | {'array':9s} | {'util':>6s} "
            f"| {'compute':>8s} | {'array':>8s} | {'e2e':>8s} "
            f"| {'binding':11s} | feas |")
    # separator widths derived from the header so columns stay in sync
    sep = "|" + "|".join("-" * len(c) for c in head.split("|")[1:-1]) + "|"
    out = [head, sep]
    for r in rows:
        out.append(
            f"| {r['bench']:12s} | {r['dtype']:7s} | {r['array']:9s} "
            f"| {r['util']:6.3f} | {r['compute']:8.2f} "
            f"| {r['array_level']:8.2f} | {r['end_to_end']:8.2f} "
            f"| {r['binding']:11s} | {str(r['feasible']):>4s} |")
    return "\n".join(out)


def run_registry(csv_rows: list | None = None,
                 target: Target = AIE_TARGET) -> list[dict]:
    rows = registry_rows(target)
    print(f"\n== Registry roofline: predict_bounds x {len(rows)} bench "
          f"cases of {len(registry.specs())} registered specs "
          f"({target.name}) ==")
    print(format_registry_table(rows))
    if csv_rows is not None:
        for r in rows:
            csv_rows.append((
                f"roofline_registry_{r['bench']}_{r['dtype']}",
                0.0,
                f"array={r['array_level']:.2f}TOPS;e2e={r['end_to_end']:.2f}"
                f"TOPS;binding={r['binding']};util={r['util']:.3f}"))
    return rows


# ---------------------------------------------------------------------------
# section 2: fused-chain HBM bytes (predicted, vs standalone launches)
# ---------------------------------------------------------------------------

#: Chain cases mirror ``benchmarks/run.py`` ``CI_CHAIN_CASES`` so the
#: structural prediction here and the timed gate rows describe the same
#: executions.
CHAIN_CASES = (
    ("conv2d+jacobi2d", ((64, 61, 4, 4), (62, 59)), "int16", None),
    ("mm+mm", ((24, 128, 64), (24, 64, 128)), "float32", ("bias_gelu",)),
)


def chain_rows(target: Target | None = None) -> list[dict]:
    """Predicted HBM bytes: one fused launch vs standalone stage launches.

    The fused launch reads the chain operands and writes the final
    output once; the unfused path additionally writes *and* re-reads the
    intermediate at the accumulate dtype — by construction that delta is
    ``FusedPlan.predicted_bytes_saved``, so the two columns are derived
    from one structural number plus the operand/output footprints
    (``jax.eval_shape``: nothing executes).
    """
    import jax
    import numpy as np

    from repro.core import fusion

    target = target or Target(name="single_chip", mesh_shape=(1, 1))
    rng = np.random.default_rng(0)
    rows: list[dict] = []
    for kind, shapes, dtype, inter in CHAIN_CASES:
        ch = fusion.chain_from_request(kind, shapes, dtype)
        plan = fusion.try_fuse(ch, target, interstage=inter)
        if plan is None:
            rows.append({"chain": kind, "dtype": dtype, "fused": False})
            continue
        ops = fusion.chain_operands(ch, rng, interstage=inter)
        out = jax.eval_shape(fusion.lower_fused(plan, backend="xla"), *ops)
        leaves = out if isinstance(out, tuple) else (out,)
        io_bytes = sum(int(o.size) * o.dtype.itemsize for o in ops)
        io_bytes += sum(int(np.prod(leaf.shape)) *
                        np.dtype(leaf.dtype).itemsize for leaf in leaves)
        unfused = io_bytes + plan.predicted_bytes_saved
        rows.append({
            "chain": kind,
            "dtype": dtype,
            "fused": True,
            "family": plan.family,
            "stages": len(ch.stages),
            "fused_bytes": io_bytes,
            "unfused_bytes": unfused,
            "bytes_saved": plan.predicted_bytes_saved,
            "saved_pct": 100.0 * plan.predicted_bytes_saved / unfused,
        })
    return rows


def format_chain_table(rows: list[dict]) -> str:
    head = (f"| {'chain':16s} | {'dtype':7s} | {'family':7s} | st "
            f"| {'fused B':>9s} | {'unfused B':>9s} | {'saved B':>8s} "
            f"| {'saved':>6s} |")
    sep = "|" + "|".join("-" * len(c) for c in head.split("|")[1:-1]) + "|"
    out = [head, sep]
    for r in rows:
        if not r["fused"]:
            out.append(f"| {r['chain']:16s} | {r['dtype']:7s} | "
                       "DID NOT FUSE |")
            continue
        out.append(
            f"| {r['chain']:16s} | {r['dtype']:7s} | {r['family']:7s} "
            f"| {r['stages']:2d} | {r['fused_bytes']:9d} "
            f"| {r['unfused_bytes']:9d} | {r['bytes_saved']:8d} "
            f"| {r['saved_pct']:5.1f}% |")
    return "\n".join(out)


def run_chains(csv_rows: list | None = None,
               target: Target | None = None) -> list[dict]:
    rows = chain_rows(target)
    print(f"\n== Fused-chain roofline: predicted HBM bytes, one fused "
          f"launch vs standalone stage launches ({len(rows)} chains) ==")
    print(format_chain_table(rows))
    if csv_rows is not None:
        for r in rows:
            if not r["fused"]:
                continue
            csv_rows.append((
                f"roofline_chain_{r['chain']}_{r['dtype']}",
                0.0,
                f"bytes_saved={r['bytes_saved']};"
                f"saved_pct={r['saved_pct']:.1f};"
                f"hbm_launches=1v{r['stages']}"))
    return rows


# ---------------------------------------------------------------------------
# section 3: dry-run artifact table (EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------

def _rl_from_json(d: dict) -> RL.Roofline:
    coll_total = sum(v for v in d["coll"].values()) if d["coll"] else 0.0
    return RL.Roofline(
        arch=d["arch"], shape=d["shape"], mesh=d["mesh"],
        chips=CHIPS[d["mesh"]],
        flops_per_chip=d["flops"],
        bytes_per_chip=d["bytes_accessed"],
        coll_bytes_per_chip=coll_total,
        t_compute=d["flops"] / V5E.bf16_flops,
        t_memory=d["bytes_accessed"] / V5E.hbm_bw,
        t_collective=coll_total / V5E.ici_bw,
        bottleneck="",
        model_flops=d["model_flops"],
        useful_ratio=d["model_flops"] / max(
            d["flops"] * CHIPS[d["mesh"]], 1.0),
        coll_breakdown=d["coll"] or {},
    )


def load(results_dir: str = "results/dryrun",
         mesh: str = "16x16") -> list[RL.Roofline]:
    rows = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        if not d.get("ok") or d["mesh"] != mesh:
            continue
        r = _rl_from_json(d)
        terms = {"compute": r.t_compute, "memory": r.t_memory,
                 "collective": r.t_collective}
        r.bottleneck = max(terms, key=terms.get)
        rows.append(r)
    return rows


def recommendation(r: RL.Roofline) -> str:
    if r.bottleneck == "collective":
        return ("move the dominant stream to a lighter collective "
                "(reduce-scatter/SP or ppermute ring) per the congestion "
                "model")
    if r.bottleneck == "memory":
        if "decode" in r.shape or "long" in r.shape:
            return "shrink cache reads: quantized KV or wider batch fusion"
        return "raise arithmetic intensity: larger per-chip tiles / fusion"
    if r.useful_ratio < 0.5:
        return "cut recompute: relax remat policy / causal block skipping"
    return "compute-bound at good efficiency: scale batch or chips"


def run_dryrun(csv_rows: list | None = None,
               results_dir: str = "results/dryrun"):
    for mesh in ("16x16", "2x16x16"):
        rows = load(results_dir, mesh)
        if not rows:
            print(f"(no dry-run results for {mesh} in {results_dir})")
            continue
        print(f"\n== Roofline table ({mesh}, {len(rows)} cells) ==")
        print(RL.format_table(rows))
        if csv_rows is not None:
            for r in rows:
                csv_rows.append((
                    f"roofline_{r.arch}_{r.shape}_{mesh}",
                    r.t_bound * 1e6,
                    f"bound={r.bottleneck};useful={r.useful_ratio:.3f};"
                    f"frac={r.roofline_fraction():.3f}"))


def run(csv_rows: list | None = None, results_dir: str = "results/dryrun"):
    run_registry(csv_rows)
    run_chains(csv_rows)
    run_dryrun(csv_rows, results_dir)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--registry-only", action="store_true",
                    help="only the registry-driven predict_bounds + "
                         "fused-chain tables (no dry-run artifacts)")
    args = ap.parse_args()
    if args.registry_only:
        run_registry()
        run_chains()
    else:
        run()
