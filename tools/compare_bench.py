#!/usr/bin/env python
"""Bench-regression gate: compare a fresh ``benchmarks/run.py --ci`` JSON
against the committed baseline (``benchmarks/BENCH_PR10.json``).

Timings from different machines are not comparable raw, so the gate is
*machine-normalized*: it computes the per-spec ratio new/baseline, takes
the median ratio as the machine-speed factor, and fails only when one
spec's ratio exceeds ``--tolerance`` (default 2.0) times that median —
i.e. when a spec got >2x slower *relative to the rest of the suite*.
Plan-cache and autotune counters are deterministic, so they compare
exactly:

  * a spec present in the baseline but missing from the fresh run fails
    (a spec was dropped from the registry or stopped benching);
  * ``plan_cache_misses`` may not increase (the spec started re-planning);
  * ``replan_hits`` must stay >= 1 (the LRU plan-cache contract);
  * ``autotune_hit`` may not flip true -> false (the spec lost its row in
    the committed crossover table and silently fell back to modelled);
  * ``hbm_round_trips`` may not grow (an execution path started
    materializing intermediates it used to keep resident).

The ``chains`` section (fused producer→consumer cases) gates
deterministically as well:

  * a chain that was ``fused`` in the baseline may not regress to
    unfused (the legality pass or a backend flip broke the fusion);
  * the fused path must keep *strictly fewer* HBM round trips than its
    unfused stage launches, and may not grow its own count;
  * fused vs unfused timings come from the *same* fresh run, so no
    machine normalization applies: ``speedup`` must stay > 1.0.

The ``hierarchy`` section (two-level serving GEMMs vs the flat
single-mesh plan, schema 5) gates:

  * a hierarchical case present in the baseline may not go missing;
  * ``hierarchical`` may not flip true -> false (planning fell back
    from the two-level composition to the flat plan: a routing
    regression);
  * ``autotune_hit`` may not flip true -> false (the case lost its
    hierarchical key in the committed crossover table);
  * ``outer_collective_bytes`` may not grow — the modelled outer
    traffic is a deterministic function of the chosen split, so growth
    means the planner picked a worse outer decomposition;
  * ``us_per_call`` is machine-normalized by the spec-suite median
    factor and fails beyond ``--tolerance``, like spec timings.

The ``serving`` section (the serving engine at one smoke arrival
rate, schema 4) gates:

  * an engine row present in the baseline may not go missing;
  * ``decode_recompiles`` may not grow (the paged engine's AOT decode
    invariant: joins/evictions edit host tables, never shapes — any
    growth means something started retracing in flight);
  * ``preemptions`` may not grow (the smoke pool is not oversubscribed,
    so a preemption means admission started over-allocating);
  * p99 latency is machine-normalized by the spec-suite median factor
    and fails beyond ``--tolerance`` (default 2x), like spec timings.

The ``streaming`` section (planned audio frontend + chunked streaming
admission, schema 6) gates:

  * a streaming row present in the baseline may not go missing;
  * ``frontend.planned_sites`` may not drop — each ``frontend.*`` call
    site must keep planning through the facade with zero fallbacks, or
    the audio pipeline silently stopped exercising the mapping path;
  * frontend planned vs XLA timings come from the same fresh run, so
    ``speedup`` gates raw against the baseline only via the
    machine-normalized ``planned_us``;
  * ``first_frame.ratio`` (offline/chunked first-logits latency) must
    stay > 1.0 — chunked admission genuinely starting decode before the
    utterance ends is the point of the row (same-run, no
    normalization);
  * ``serving.decode_compiles`` gates exactly at the baseline value
    (1): the streaming engine's decode executable is AOT-compiled once
    for its whole life;
  * ``serving.steady_plan_misses`` / ``steady_measure_calls`` /
    ``steady_prefill_compiles`` may not grow — an identical second
    audio stream must replan, re-measure, and retrace *nothing*.

    python tools/compare_bench.py benchmarks/BENCH_PR10.json BENCH_NEW.json

Exit code 0 = within tolerance, 1 = regression.  Dependency-free.
"""

from __future__ import annotations

import argparse
import json
import sys


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def compare(baseline: dict, fresh: dict, tolerance: float) -> list[str]:
    errors: list[str] = []
    base_specs = baseline.get("specs", {})
    new_specs = fresh.get("specs", {})

    missing = sorted(set(base_specs) - set(new_specs))
    for name in missing:
        errors.append(f"{name}: in baseline but missing from fresh run")
    added = sorted(set(new_specs) - set(base_specs))
    for name in added:
        print(f"note: {name} is new (no baseline) — seed it on the next "
              "baseline refresh")

    common = sorted(set(base_specs) & set(new_specs))
    ratios = {}
    for name in common:
        b, n = base_specs[name], new_specs[name]
        if n.get("plan_cache_misses", 0) > b.get("plan_cache_misses", 0):
            errors.append(
                f"{name}: plan-cache misses grew "
                f"{b.get('plan_cache_misses')} -> "
                f"{n.get('plan_cache_misses')} (spec re-plans)")
        if n.get("replan_hits", 1) < 1:
            errors.append(
                f"{name}: re-planning the same recurrence missed the LRU "
                "plan cache")
        if b.get("autotune_hit", False) and not n.get("autotune_hit", False):
            errors.append(
                f"{name}: autotune table hit became a miss — the spec "
                "lost its committed crossover-table coverage (regenerate "
                "with tools/gen_autotune.py)")
        if n.get("hbm_round_trips", 1) > b.get("hbm_round_trips", 1):
            errors.append(
                f"{name}: HBM round trips grew "
                f"{b.get('hbm_round_trips')} -> {n.get('hbm_round_trips')}")
        if b.get("us_per_call", 0) > 0:
            ratios[name] = n["us_per_call"] / b["us_per_call"]

    med = 1.0
    if ratios:
        med = _median(list(ratios.values()))
        print(f"machine-speed factor (median new/baseline): {med:.2f}x")
        for name in common:
            if name not in ratios:
                continue
            rel = ratios[name] / max(med, 1e-9)
            flag = "REGRESSED" if rel > tolerance else "ok"
            print(f"  {name:14s} base={base_specs[name]['us_per_call']:10.1f}us "
                  f"new={new_specs[name]['us_per_call']:10.1f}us "
                  f"rel={rel:5.2f}x  {flag}")
            if rel > tolerance:
                errors.append(
                    f"{name}: {rel:.2f}x slower than the suite median "
                    f"(tolerance {tolerance:.1f}x)")
    errors += compare_chains(baseline, fresh)
    errors += compare_hierarchy(baseline, fresh, med, tolerance)
    errors += compare_serving(baseline, fresh, med, tolerance)
    errors += compare_streaming(baseline, fresh, med, tolerance)
    return errors


def compare_hierarchy(baseline: dict, fresh: dict, machine_factor: float,
                      tolerance: float) -> list[str]:
    """Gates for the two-level serving-GEMM rows (docstring above)."""
    errors: list[str] = []
    base = baseline.get("hierarchy", {})
    new = fresh.get("hierarchy", {})
    for name in sorted(set(base) - set(new)):
        errors.append(
            f"hierarchy {name}: in baseline but missing from fresh run")
    for name in sorted(set(base) & set(new)):
        b, n = base[name], new[name]
        print(f"  hierarchy {name:6s} split={n.get('outer_split')} "
              f"bytes={n.get('outer_collective_bytes')} "
              f"hier={n.get('us_per_call', 0):10.1f}us "
              f"flat={n.get('flat_us_per_call', 0):10.1f}us "
              f"backend={n.get('backend')}"
              f"[{'hit' if n.get('autotune_hit') else 'miss'}]")
        if b.get("hierarchical", False) and not n.get("hierarchical",
                                                      False):
            errors.append(
                f"hierarchy {name}: planned two-level in the baseline "
                "but the fresh run fell back to the flat plan (outer-"
                "split legality or routing regression)")
            continue
        if b.get("autotune_hit", False) and not n.get("autotune_hit",
                                                      False):
            errors.append(
                f"hierarchy {name}: autotune table hit became a miss — "
                "the case lost its hierarchical key in the committed "
                "crossover table (regenerate with tools/gen_autotune.py "
                "--merge)")
        if (n.get("outer_collective_bytes", 0)
                > b.get("outer_collective_bytes", 0)):
            errors.append(
                f"hierarchy {name}: outer collective bytes grew "
                f"{b.get('outer_collective_bytes')} -> "
                f"{n.get('outer_collective_bytes')} (the planner picked "
                "a worse outer split; deterministic, no normalization "
                "applies)")
        if b.get("us_per_call", 0) > 0:
            rel = (n.get("us_per_call", 0) / b["us_per_call"]) / max(
                machine_factor, 1e-9)
            if rel > tolerance:
                errors.append(
                    f"hierarchy {name}: {rel:.2f}x slower than the "
                    f"machine-normalized baseline (tolerance "
                    f"{tolerance:.1f}x)")
    return errors


def compare_serving(baseline: dict, fresh: dict, machine_factor: float,
                    tolerance: float) -> list[str]:
    """Gates for the serving rows (docstring above)."""
    errors: list[str] = []
    base = baseline.get("serving", {})
    new = fresh.get("serving", {})
    for kind in sorted(set(base) - set(new)):
        errors.append(
            f"serving {kind}: in baseline but missing from fresh run")
    for kind in sorted(set(base) & set(new)):
        b, n = base[kind], new[kind]
        print(f"  serving {kind:5s} tok/s={n.get('tokens_per_sec', 0):8.2f} "
              f"p99={n.get('p99_ms', 0):8.1f}ms "
              f"preempt={n.get('preemptions', 0)} "
              f"recompiles={n.get('decode_recompiles', 0)}")
        if n.get("decode_recompiles", 0) > b.get("decode_recompiles", 0):
            errors.append(
                f"serving {kind}: decode recompiles grew "
                f"{b.get('decode_recompiles')} -> "
                f"{n.get('decode_recompiles')} — in-flight joins/"
                "evictions must never retrace the AOT decode executable")
        if n.get("preemptions", 0) > b.get("preemptions", 0):
            errors.append(
                f"serving {kind}: preemptions grew "
                f"{b.get('preemptions')} -> {n.get('preemptions')} on a "
                "pool that is not oversubscribed")
        if b.get("p99_ms", 0) > 0:
            rel = (n.get("p99_ms", 0) / b["p99_ms"]) / max(
                machine_factor, 1e-9)
            if rel > tolerance:
                errors.append(
                    f"serving {kind}: p99 latency {rel:.2f}x the "
                    f"machine-normalized baseline (tolerance "
                    f"{tolerance:.1f}x)")
    return errors


def compare_streaming(baseline: dict, fresh: dict, machine_factor: float,
                      tolerance: float) -> list[str]:
    """Gates for the streaming audio rows (docstring above)."""
    errors: list[str] = []
    base = baseline.get("streaming", {})
    new = fresh.get("streaming", {})
    for row in sorted(set(base) - set(new)):
        errors.append(
            f"streaming {row}: in baseline but missing from fresh run")

    if "frontend" in base and "frontend" in new:
        b, n = base["frontend"], new["frontend"]
        print(f"  streaming frontend planned={n.get('planned_us', 0):8.1f}us "
              f"xla={n.get('xla_us', 0):8.1f}us "
              f"x{n.get('speedup', 0):.2f} "
              f"sites={n.get('planned_sites', 0)}")
        if n.get("planned_sites", 0) < b.get("planned_sites", 0):
            errors.append(
                f"streaming frontend: planned call sites dropped "
                f"{b.get('planned_sites')} -> {n.get('planned_sites')} — "
                "a frontend stage stopped planning through the facade "
                "(or started falling back); deterministic, no "
                "normalization applies")
        if b.get("planned_us", 0) > 0:
            rel = (n.get("planned_us", 0) / b["planned_us"]) / max(
                machine_factor, 1e-9)
            if rel > tolerance:
                errors.append(
                    f"streaming frontend: planned chunk {rel:.2f}x slower "
                    f"than the machine-normalized baseline (tolerance "
                    f"{tolerance:.1f}x)")

    if "first_frame" in base and "first_frame" in new:
        b, n = base["first_frame"], new["first_frame"]
        print(f"  streaming first-frame chunked={n.get('chunked_us', 0):8.1f}us "
              f"offline={n.get('offline_us', 0):8.1f}us "
              f"x{n.get('ratio', 0):.2f}")
        if n.get("ratio", 0) <= 1.0:
            errors.append(
                f"streaming first-frame: chunked admission no longer "
                f"beats the offline whole-utterance path to first logits "
                f"(ratio {n.get('ratio')}; same-run timings, no machine "
                "normalization applies)")
        if b.get("chunked_us", 0) > 0:
            rel = (n.get("chunked_us", 0) / b["chunked_us"]) / max(
                machine_factor, 1e-9)
            if rel > tolerance:
                errors.append(
                    f"streaming first-frame: chunked latency {rel:.2f}x "
                    f"the machine-normalized baseline (tolerance "
                    f"{tolerance:.1f}x)")

    if "serving" in base and "serving" in new:
        b, n = base["serving"], new["serving"]
        print(f"  streaming serving decode_compiles="
              f"{n.get('decode_compiles', 0)} "
              f"steady misses={n.get('steady_plan_misses', 0)} "
              f"measures={n.get('steady_measure_calls', 0)} "
              f"prefill_compiles={n.get('steady_prefill_compiles', 0)}")
        if n.get("decode_compiles", 0) != b.get("decode_compiles", 1):
            errors.append(
                f"streaming serving: decode_compiles "
                f"{b.get('decode_compiles')} -> {n.get('decode_compiles')}"
                " — the streaming engine's decode executable must be "
                "AOT-compiled exactly once for its whole life")
        for key in ("steady_plan_misses", "steady_measure_calls",
                    "steady_prefill_compiles"):
            if n.get(key, 0) > b.get(key, 0):
                errors.append(
                    f"streaming serving: {key} grew {b.get(key)} -> "
                    f"{n.get(key)} — an identical second audio stream "
                    "must retrace nothing (deterministic, gated exactly)")
    return errors


def compare_chains(baseline: dict, fresh: dict) -> list[str]:
    """Deterministic gates for the fused-chain rows (docstring above)."""
    errors: list[str] = []
    base = baseline.get("chains", {})
    new = fresh.get("chains", {})
    for name in sorted(set(base) - set(new)):
        errors.append(
            f"chain {name}: in baseline but missing from fresh run")
    for name in sorted(set(base) & set(new)):
        b, n = base[name], new[name]
        if b.get("fused", False) and not n.get("fused", False):
            errors.append(
                f"chain {name}: was fused in the baseline but the fresh "
                "run fell back to unfused stage launches (fusion "
                "legality or backend flip regression)")
            continue
        if not n.get("fused", False):
            continue
        bh = b.get("hbm_round_trips", {})
        nh = n.get("hbm_round_trips", {})
        print(f"  chain {name:18s} fused={n.get('fused_us', 0):10.1f}us "
              f"unfused={n.get('unfused_us', 0):10.1f}us "
              f"x{n.get('speedup', 0):.2f} "
              f"hbm {nh.get('fused')} vs {nh.get('unfused')}")
        if nh.get("fused", 1) > bh.get("fused", 1):
            errors.append(
                f"chain {name}: fused HBM round trips grew "
                f"{bh.get('fused')} -> {nh.get('fused')}")
        if nh.get("fused", 1) >= nh.get("unfused", 2):
            errors.append(
                f"chain {name}: the fused path no longer has strictly "
                f"fewer HBM round trips ({nh.get('fused')} vs "
                f"{nh.get('unfused')})")
        if b.get("autotune_hit", False) and not n.get("autotune_hit",
                                                      False):
            errors.append(
                f"chain {name}: autotune table hit became a miss — the "
                "chain lost its committed crossover-table coverage")
        if n.get("speedup", 0) <= 1.0:
            errors.append(
                f"chain {name}: fused no longer beats the summed unfused "
                f"stage launches (speedup {n.get('speedup')}; same-run "
                "timings, no machine normalization applies)")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline", help="committed BENCH_PR10.json")
    ap.add_argument("fresh", help="fresh run.py --ci output")
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="allowed per-spec slowdown relative to the "
                         "suite-median machine factor (default 2.0)")
    args = ap.parse_args()
    with open(args.baseline, encoding="utf-8") as f:
        baseline = json.load(f)
    with open(args.fresh, encoding="utf-8") as f:
        fresh = json.load(f)
    errors = compare(baseline, fresh, args.tolerance)
    for e in errors:
        print(f"FAIL {e}")
    n = len(baseline.get("specs", {}))
    print(f"compare_bench: {n} baseline specs -> "
          f"{'FAILED' if errors else 'OK'}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
