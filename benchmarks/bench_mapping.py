"""Mapping quality benchmarks — a thin driver over the autotuner.

1. Algorithm 1 vs naive PLIO placement: max column congestion across array
   shapes (the paper's 'constraints make compilation succeed' claim,
   quantified).
2. Measured backend crossover: ``core.autotune.race`` times every backend
   each spec can run in-process (pallas vs XLA at mesh 1x1) and reports
   the winner next to the committed default table's entry — the same
   measurement ``tools/gen_autotune.py`` persists, run live.  The
   chip-level schedules (systolic/allgather) run on a real 2x2 mesh in
   ``chip_smoke.py --chips 4``, one process holding all four chips.
3. Table IV analogue: WideSA (AIE) vs PL-only (AutoSA) energy-efficiency
   ratios recomputed from the paper's numbers against our bounds.
"""

from __future__ import annotations

import time

from repro.core import AIE_TARGET, Target, autotune, enumerate_schedules, matmul
from repro.core.plio import assign_plios, build_mapped_graph, congestion, naive_assignment
from repro.kernels import registry

# specs raced in-process for section 2; smoke shapes keep interpret-mode
# pallas affordable while still crossing the pallas/XLA break-even
_RACE_SPECS = ("mm", "jacobi2d", "fir", "mttkrp")


def run(csv_rows: list):
    print("\n== Algorithm 1 vs naive PLIO placement (max congestion) ==")
    rec = matmul(8192, 8192, 8192)
    sched = next(s for s in enumerate_schedules(rec)
                 if s.space_loops == ("i", "j"))
    print(f"{'array':>8s} {'alg1':>6s} {'naive':>6s} {'gain':>6s}")
    for shape in [(4, 8), (8, 16), (8, 32), (8, 50)]:
        t0 = time.perf_counter()
        g = build_mapped_graph(rec, sched, shape, ports_per_edge=4)
        a1 = assign_plios(g, ports_per_col=4)
        us = (time.perf_counter() - t0) * 1e6
        w1, e1 = congestion(g, a1)
        c1 = max(max(w1), max(e1))
        nv = naive_assignment(g)
        w0, e0 = congestion(g, nv)
        c0 = max(max(w0), max(e0))
        print(f"{shape[0]}x{shape[1]:>4d} {c1:6d} {c0:6d} "
              f"{c0 / max(c1, 1):6.2f}x")
        csv_rows.append(
            (f"plio_alg1_{shape[0]}x{shape[1]}", us,
             f"cong={c1};naive={c0};rc={AIE_TARGET.rc}"))

    print("\n== measured backend crossover (autotune race, mesh 1x1) ==")
    target = Target(name="single_chip", mesh_shape=(1, 1))
    policy = autotune.PlanPolicy(mode="measured", reps=3, warmup=1)
    try:
        committed = autotune.load_table(autotune.DEFAULT_TABLE_PATH)
    except autotune.TableError:
        committed = {"entries": {}}
    for name in _RACE_SPECS:
        spec = registry.get(name)
        rec = spec.builder(*spec.smoke_args, spec.parity_dtypes[0])
        res = autotune.race(rec, target, policy,
                            backends=("pallas", "xla"))
        entry = committed["entries"].get(
            autotune.autotune_key(rec, target.mesh_shape), {})
        agree = ("=table" if entry.get("backend") == res["backend"]
                 else f"table={entry.get('backend', '?')}")
        times = "  ".join(f"{b}={u:9.1f}us" for b, u in
                          sorted(res["us"].items()))
        print(f"  {name:13s} {times}  -> {res['backend']} ({agree})")
        csv_rows.append(
            (f"autotune_race_{name}", res["us"][res["backend"]],
             f"winner={res['backend']};{agree}"))

    print("\n== Table IV analogue (energy-efficiency ratios, from paper) ==")
    # paper Table IV: norm. TOPS/W of WideSA vs PL-only
    for dtype, ratio in [("float32", 2.25), ("int8", 1.94),
                         ("int16", 1.29), ("int32", 2.25)]:
        print(f"  MM {dtype:8s}: WideSA {ratio:.2f}x PL-only TOPS/W "
              f"(paper), AIEs 400 vs DSPs ~1530")
        csv_rows.append((f"table4_mm_{dtype}", 0.0,
                         f"widesa_over_plonly={ratio}"))
