"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV at the end (harness contract).

    PYTHONPATH=src python -m benchmarks.run [--only recurrences,...]

``--ci`` runs the bench-regression gate's measurement pass instead: one
plan-driven smoke execution per registered spec (timing + plan-cache +
autotune counters + HBM round-trip counts) written as JSON, plus one
row per **fused chain** (conv2d→jacobi2d, the mm→mm MLP pair) timing
the fused single-launch execution against the same stages as separate
launches with the intermediate forced through HBM.  Planning consults
the committed autotune crossover table under ``PlanPolicy(mode="cached")``
— each row records which measured backend won and whether the table was
hit — and execution dispatches to that winner.  Schema 5 adds
**hierarchical rows**: each serving GEMM case planned under the
two-level serving target vs the flat single-mesh plan, with the
modelled outer collective bytes gated exactly.  Schema 6 adds
**streaming rows**: the planned audio frontend (FIR -> fused fft2d
chain -> conv2d) vs the same math with the facade disabled, the
chunked-admission first-logits latency vs the offline whole-utterance
path, and the paged engine's steady-state retrace counters over an
identical second audio stream (decode compiles pinned at 1, plan-cache
misses / measure calls / prefill compiles pinned at 0).  CI compares
the fresh file against the committed ``benchmarks/BENCH_PR10.json``
baseline with ``tools/compare_bench.py`` (ratios are
machine-normalized, so only real >2x per-spec regressions fail the
gate; a fused chain case flipping back to unfused, a hierarchical row
flipping back to flat, growing HBM round trips or outer collective
bytes, a frontend site losing its plan, or any steady-state streaming
retrace fail deterministically).

    PYTHONPATH=src python benchmarks/run.py --ci --out BENCH_NEW.json
"""

import argparse
import json
import sys
import time


def ci_bench(out_path: str) -> dict:
    """Per-spec smoke timings + plan-cache/autotune counts for the gate.

    For every registered KernelSpec: build the smoke-size recurrence on
    its first parity dtype, plan it under ``PlanPolicy(mode="cached")``
    (the committed crossover table supplies the measured winner — no
    timing happens at plan time), execute through the winner backend's
    lowering (compile excluded), and record

      * ``us_per_call``        — mean of 3 timed calls (interpret mode on
                                 CPU: a *relative* smoke number, compared
                                 against the baseline only after machine
                                 normalization);
      * ``backend``            — the measured winner dispatched to;
      * ``autotune_hit``       — whether planning hit the committed table
                                 (a true -> false flip means a spec lost
                                 its table coverage: a real regression);
      * ``plan_cache_misses``  — cache misses this spec's planning cost
                                 (deterministic: a growth means the spec
                                 started re-planning, a real regression);
      * ``replan_hits``        — extra hits when re-planning the same
                                 recurrence (must stay >= 1: the LRU cache
                                 contract);
      * ``hbm_round_trips``    — HBM materialization points per call (a
                                 standalone launch flushes its output
                                 once; deterministic, gated exactly).

    The ``chains`` section runs each fused case twice per call shape:
    ``fused`` (one launch, intermediate shard-/fusion-resident) and
    ``unfused`` (one launch per stage, ``block_until_ready`` between, so
    the intermediate round-trips HBM like two standalone plans).  The
    fused path must be strictly cheaper in round trips (1 vs n_stages)
    and, machine-normalized, in time.
    """
    import numpy as np
    import jax.numpy as jnp

    from repro.core import PlanPolicy, Target, best_plan
    from repro.core.autotune import counters
    from repro.core.codegen import lower_plan
    from repro.core.mapper import plan_cache_clear, plan_cache_info
    from repro.kernels import registry

    target = Target(name="single_chip", mesh_shape=(1, 1))
    policy = PlanPolicy(mode="cached")
    plan_cache_clear()
    rng = np.random.default_rng(0)
    specs_out: dict = {}
    for spec in registry.specs():
        dtype = spec.parity_dtypes[0]
        misses_before = plan_cache_info().misses
        measured_before = counters()["measure_calls"]
        rec = spec.builder(*spec.smoke_args, dtype)
        plan = best_plan(rec, target, policy=policy)
        assert counters()["measure_calls"] == measured_before, \
            "cached policy must not time at plan time"
        mesh = None
        if plan.backend in ("systolic", "allgather"):
            from repro.compat import make_mesh
            mesh = make_mesh(target.mesh_shape, ("row", "col"))
        fn = lower_plan(plan, backend=plan.backend, mesh=mesh)
        operands = spec.operands(rec, rng)
        fn(*operands)  # compile outside the timed loop
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*operands)
            for leaf in out if isinstance(out, tuple) else (out,):
                jnp.asarray(leaf).block_until_ready()
        us = (time.perf_counter() - t0) / reps * 1e6
        hits_before = plan_cache_info().hits
        best_plan(spec.builder(*spec.smoke_args, dtype), target,
                  policy=policy)
        specs_out[spec.name] = {
            "dtype": dtype,
            "us_per_call": round(us, 1),
            "backend": plan.backend,
            "autotune_hit": plan.provenance == "measured",
            "plan_cache_misses": plan_cache_info().misses - misses_before,
            "replan_hits": plan_cache_info().hits - hits_before,
            "hbm_round_trips": 1,  # one launch, one output flush
        }
        print(f"ci-bench {spec.name:13s} {dtype:8s} {us:10.1f} us  "
              f"backend={plan.backend}"
              f"[{'hit' if plan.provenance == 'measured' else 'miss'}] "
              f"misses={specs_out[spec.name]['plan_cache_misses']} "
              f"replan_hits={specs_out[spec.name]['replan_hits']}")
    chains_out = _ci_bench_chains(target, policy, rng)
    hierarchy_out = _ci_bench_hierarchy(policy, rng)
    serving_out = _ci_bench_serving()
    streaming_out = _ci_bench_streaming()
    payload = {
        "schema": 6,
        "note": ("per-spec smoke timings (interpret mode, autotuned "
                 "backend) + plan-cache/autotune counters + HBM "
                 "round-trip counts, plus fused-chain rows (fused vs "
                 "unfused stage launches), hierarchical rows (two-level "
                 "serving GEMMs vs the flat single-mesh plan: outer "
                 "collective bytes gate exactly), serving rows "
                 "(the serving engine at one smoke arrival rate) and "
                 "streaming rows (planned audio frontend vs XLA, "
                 "chunked vs offline first-frame latency, steady-state "
                 "retrace counters gated exactly); compare with "
                 "tools/compare_bench.py, never raw across machines"),
        "specs": specs_out,
        "chains": chains_out,
        "hierarchy": hierarchy_out,
        "serving": serving_out,
        "streaming": streaming_out,
    }
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"ci-bench: wrote {out_path} ({len(specs_out)} specs, "
          f"{len(chains_out)} chains)")
    return payload


#: Fused-chain gate cases: the worked stencil pair and the serving MLP
#: up->down pair (the shape the committed table's chain keys record).
CI_CHAIN_CASES = (
    ("conv2d+jacobi2d", ((64, 61, 4, 4), (62, 59)), "int16", None),
    ("mm+mm", ((24, 128, 64), (24, 64, 128)), "float32", ("bias_gelu",)),
)


def _ci_bench_chains(target, policy, rng) -> dict:
    """Fused vs unfused timings for the registered chain cases.

    ``fused``: ONE jitted launch for the whole chain (the plan's
    table-measured composition backend).  ``unfused``: one jitted launch
    per stage through each stage's own cached plan, with
    ``block_until_ready`` between stages — the intermediate materializes
    to HBM exactly as two standalone plans would.  HBM round trips are
    counted at those materialization points (fused: 1, unfused:
    n_stages), so the fused row must be *strictly* lower.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import best_plan
    from repro.core import fusion
    from repro.core.autotune import apply_policy
    from repro.core.codegen import lower_plan

    out: dict = {}
    for kind, shapes, dtype, inter in CI_CHAIN_CASES:
        ch = fusion.chain_from_request(kind, shapes, dtype)
        plan = fusion.try_fuse(ch, target, interstage=inter)
        row: dict = {"dtype": dtype, "fused": plan is not None}
        if plan is not None:
            plan = apply_policy(plan, policy)
            avail = fusion.fused_available_backends(plan)
            backend = plan.backend if plan.backend in avail else "xla"
            row["backend"] = backend
            row["autotune_hit"] = plan.provenance == "measured"
            row["predicted_bytes_saved"] = plan.predicted_bytes_saved
            ops = fusion.chain_operands(ch, rng, interstage=inter)
            fused_fn = jax.jit(fusion.lower_fused(plan, backend=backend))
            stage_ops, biases = fusion.split_operands(plan, ops)
            # unfused: per-stage cached plans, one launch per stage
            stage_fns = []
            for i, st in enumerate(ch.stages):
                sp = best_plan(st, target, policy=policy)
                b = sp.backend if sp.backend in ("xla", "pallas") else "xla"
                low = lower_plan(sp, backend=b)
                if i == 0 or plan.interstage[i - 1] is None:
                    stage_fns.append(jax.jit(low))
                else:
                    op = plan.interstage[i - 1]
                    stage_fns.append(jax.jit(
                        lambda mid, bias, *rest, _low=low, _op=op:
                        _low(fusion.interstage_apply(_op, mid, bias),
                             *rest)))

            def block(x):
                for leaf in x if isinstance(x, tuple) else (x,):
                    jnp.asarray(leaf).block_until_ready()
                return x

            def unfused_call():
                cur = block(stage_fns[0](*stage_ops[0]))
                for b_i in range(len(ch.stages) - 1):
                    nxt = stage_fns[b_i + 1]
                    if plan.interstage[b_i] is None:
                        cur = nxt(cur, *stage_ops[b_i + 1])
                    else:
                        cur = nxt(cur, biases[b_i], *stage_ops[b_i + 1])
                    cur = block(cur)
                return cur

            block(fused_fn(*ops))  # compile outside the timed loop
            unfused_call()
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                block(fused_fn(*ops))
            fused_us = (time.perf_counter() - t0) / reps * 1e6
            t0 = time.perf_counter()
            for _ in range(reps):
                unfused_call()
            unfused_us = (time.perf_counter() - t0) / reps * 1e6
            row.update({
                "fused_us": round(fused_us, 1),
                "unfused_us": round(unfused_us, 1),
                "speedup": round(unfused_us / fused_us, 3),
                "hbm_round_trips": {"fused": 1,
                                    "unfused": len(ch.stages)},
            })
            print(f"ci-bench chain {kind:18s} {dtype:8s} "
                  f"fused={fused_us:8.1f}us unfused={unfused_us:8.1f}us "
                  f"x{row['speedup']:.2f} backend={backend}"
                  f"[{'hit' if row['autotune_hit'] else 'miss'}] "
                  f"hbm 1 vs {len(ch.stages)}")
        else:
            print(f"ci-bench chain {kind:18s} {dtype:8s} DID NOT FUSE")
        out[kind] = row
    return out


#: Hierarchical gate cases: serving GEMM shapes the committed table
#: covers under the serving hierarchical target's outer|mesh keys.
CI_HIERARCHY_CASES = (
    ("mm", (24, 128, 64), "float32"),
    ("bmm", (8, 12, 16, 12), "float32"),
)


def _ci_bench_hierarchy(policy, rng) -> dict:
    """Two-level serving-GEMM rows vs the flat single-mesh plan.

    Each case plans the same recurrence twice — under
    ``SERVING_HIERARCHICAL_TARGET`` (outer ``(dp, tp)`` Megatron split x
    inner chip mesh) and under the flat inner-mesh ``Target`` — then
    times both lowered executions.  ``outer_collective_bytes`` is the
    plan's modelled outer traffic (the ring identities in
    ``parallel/collectives.py``), fully deterministic, so the gate pins
    it exactly: growth means the planner picked a worse outer split.
    ``hierarchical`` records that planning actually produced a
    two-level plan — a flip back to flat is a routing regression.
    """
    import time

    import jax.numpy as jnp

    from repro.core import SERVING_HIERARCHICAL_TARGET, Target, best_plan
    from repro.core.codegen import lower_plan
    from repro.kernels import registry

    ht = SERVING_HIERARCHICAL_TARGET
    flat = Target(name="flat_chip", mesh_shape=ht.mesh_shape)
    out: dict = {}
    for kind, bargs, dtype in CI_HIERARCHY_CASES:
        spec = registry.get(kind)
        rec = spec.builder(*bargs, dtype)
        plan = best_plan(rec, ht, policy=policy)
        fplan = best_plan(rec, flat, policy=policy)
        # under jit-free CI timing the traceable compositions race;
        # chip backends need dp*tp disjoint inner meshes (not on CI)
        backend = plan.backend if plan.backend in ("xla", "pallas") else "xla"
        fbackend = (fplan.backend if fplan.backend in ("xla", "pallas")
                    else "xla")
        fn = lower_plan(plan, backend=backend)
        ffn = lower_plan(fplan, backend=fbackend)
        operands = spec.operands(rec, rng)

        def timed(f):
            jnp.asarray(f(*operands)).block_until_ready()  # compile
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                jnp.asarray(f(*operands)).block_until_ready()
            return (time.perf_counter() - t0) / reps * 1e6

        us, flat_us = timed(fn), timed(ffn)
        row = {
            "dtype": dtype,
            "hierarchical": hasattr(plan, "outer_split"),
            "outer_split": getattr(plan, "outer_split", None),
            "backend": backend,
            "autotune_hit": plan.provenance == "measured",
            "outer_collective_bytes": int(getattr(plan, "outer_bytes", 0)),
            "us_per_call": round(us, 1),
            "flat_backend": fbackend,
            "flat_us_per_call": round(flat_us, 1),
        }
        out[kind] = row
        print(f"ci-bench hier {kind:6s} {dtype:8s} "
              f"split={row['outer_split']} "
              f"bytes={row['outer_collective_bytes']} "
              f"hier={us:8.1f}us flat={flat_us:8.1f}us "
              f"backend={backend}"
              f"[{'hit' if row['autotune_hit'] else 'miss'}]")
    return out


#: Serving smoke workload: one arrival rate, one seeded request stream.
#: Chosen so the queue actually builds without oversubscribing the
#: block pool (preemptions stay deterministic: 0).
CI_SERVING_CASE = dict(arch="qwen1.5-0.5b", rate=8.0, requests=10,
                       max_new=4, lanes=4, max_seq=64, block_size=8,
                       seed=0)


def _ci_bench_serving() -> dict:
    """The serving engine's row for the gate, keyed ``paged``.

    Latencies are wall-time measurements (machine-normalized by the
    comparator like the spec timings); ``decode_recompiles`` and
    ``preemptions`` are deterministic and gate exactly — the engine's
    AOT invariant pins recompiles at 0."""
    try:
        from benchmarks.bench_serving import (build_engine, make_requests,
                                              run_load, warmup)
    except ModuleNotFoundError:
        # invoked as `python benchmarks/run.py`: sys.path[0] is the
        # benchmarks dir itself, not the repo root
        from bench_serving import (build_engine, make_requests, run_load,
                                   warmup)

    case = dict(CI_SERVING_CASE)
    arch, rate = case.pop("arch"), case.pop("rate")
    n, seed = case.pop("requests"), case.pop("seed")
    max_new = case.pop("max_new")
    cfg, eng = build_engine(arch, max_lanes=case["lanes"],
                            max_seq=case["max_seq"],
                            block_size=case["block_size"])
    warmup(eng, cfg, max_new=max_new)
    reqs = make_requests(cfg, n, seed=seed, max_new=max_new)
    row = run_load(eng, reqs, rate=rate, seed=seed)
    row["arch"] = arch
    print(f"ci-bench serving paged {arch:13s} rate={rate:.0f}/s "
          f"tok/s={row['tokens_per_sec']:8.2f} "
          f"p99={row['p99_ms']:8.1f}ms "
          f"preempt={row['preemptions']} "
          f"recompiles={row['decode_recompiles']}")
    return {"paged": row}


#: Streaming smoke workload: the audio-frontend chunk pipeline plus a
#: paged whisper-base engine fed the identical audio stream twice — the
#: second drain is the zero-retrace steady state the gate pins.
CI_STREAMING_CASE = dict(arch="whisper-base", chunks=4, max_new=4,
                         lanes=2, max_seq=64, block_size=8, seed=0)


def _ci_bench_streaming() -> dict:
    """Streaming audio rows for the gate (schema 6).

    * ``frontend`` — one chunk through the planned FIR -> fused fft2d
      chain -> conv2d pipeline vs the *same* math traced with the facade
      disabled (pure XLA reference lowering).  ``speedup`` is a same-run
      ratio (no machine normalization); ``planned_sites`` counts the
      ``frontend.*`` report sites that actually planned with zero
      fallbacks — it may not drop, or the frontend silently stopped
      exercising the mapping pipeline.
    * ``first_frame`` — time-to-first-logits of the chunked admission
      path (ONE chunk of frontend + encoder + the decoder prompt pass
      against the partial enc cache) vs the offline whole-utterance path
      (every chunk before any decode).  Decode genuinely starts before
      the utterance ends iff ``ratio`` (offline/chunked) stays > 1;
      same-run, gated raw.
    * ``serving`` — a paged whisper-base engine drains one audio stream
      end to end (warm pass: every per-chunk jit compiles), then drains
      an identical second stream.  Plan-cache misses, autotune
      measurements and prefill/decode compiles across the second drain
      are the steady-state counters — deterministic, gated exactly at
      zero, with ``decode_compiles`` pinned at 1 for the engine's life.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.core.autotune import counters
    from repro.core.mapper import plan_cache_info
    from repro.kernels import planned
    from repro.models import build_model
    from repro.models import encdec
    from repro.models.model import cache_dtype_of
    from repro.serve import AudioFrontend, FrontendConfig, synth_samples
    try:
        from benchmarks.bench_serving import build_engine
    except ModuleNotFoundError:
        from bench_serving import build_engine

    case = dict(CI_STREAMING_CASE)
    arch = case["arch"]
    cfg = get_smoke_config(arch)
    fc = FrontendConfig(d_model=cfg.d_model)
    samples = synth_samples(fc, case["chunks"], seed=case["seed"])

    def timed(fn, reps=3):
        fn()  # compile outside the timed loop
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e6

    # frontend: fresh trace per facade mode (the jit caches the facade
    # decision at trace time, so each mode needs its own AudioFrontend)
    fe = AudioFrontend(fc)
    chunk = jnp.asarray(fe.split(samples)[0])
    carry = fe.init_state()
    before = planned.planned_report()
    jax.block_until_ready(fe.chunk_features(carry, chunk))
    delta = planned.report_delta(before, planned.planned_report())
    planned_sites = sum(
        1 for site, row in delta.items()
        if site.startswith("frontend.") and row.get("planned", 0) > 0
        and row.get("fallback", 0) == 0)
    planned_us = timed(lambda: jax.block_until_ready(
        fe.chunk_features(carry, chunk)))
    fe_xla = AudioFrontend(fc)
    with planned.override(enabled=False):
        jax.block_until_ready(fe_xla.chunk_features(carry, chunk))
    xla_us = timed(lambda: jax.block_until_ready(
        fe_xla.chunk_features(carry, chunk)))
    frontend_row = {
        "dtype": fc.dtype,
        "planned_us": round(planned_us, 1),
        "xla_us": round(xla_us, 1),
        "speedup": round(xla_us / planned_us, 3),
        "planned_sites": planned_sites,
    }
    print(f"ci-bench stream frontend   {fc.dtype:8s} "
          f"planned={planned_us:8.1f}us xla={xla_us:8.1f}us "
          f"x{frontend_row['speedup']:.2f} sites={planned_sites}")

    # first frame: chunked admission vs offline whole-utterance prefill
    params = build_model(cfg).init(jax.random.PRNGKey(42))
    cdt = cache_dtype_of(cfg)
    C = fc.frames_per_chunk
    tokens = jnp.zeros((1, 1), jnp.int32)
    max_seq = case["max_seq"]

    def first_chunked():
        _, feats = fe.chunk_features(fe.init_state(), chunk)
        logits, _, _ = encdec.prefill_streaming(
            params, cfg, feats[None], tokens, max_seq, C, cache_dtype=cdt)
        jax.block_until_ready(logits)

    def first_offline():
        feats = fe.offline_features(samples)
        logits, _, _ = encdec.prefill_streaming(
            params, cfg, feats[None], tokens, max_seq, C, cache_dtype=cdt)
        jax.block_until_ready(logits)

    chunked_us = timed(first_chunked)
    offline_us = timed(first_offline)
    first_frame_row = {
        "chunks": case["chunks"],
        "chunked_us": round(chunked_us, 1),
        "offline_us": round(offline_us, 1),
        "ratio": round(offline_us / chunked_us, 3),
    }
    print(f"ci-bench stream first-frame chunked={chunked_us:8.1f}us "
          f"offline={offline_us:8.1f}us x{first_frame_row['ratio']:.2f}")

    # serving steady state: identical second stream must retrace nothing
    _, eng = build_engine(arch, max_lanes=case["lanes"],
                          max_seq=case["max_seq"],
                          block_size=case["block_size"])
    eng.submit_audio_stream(samples, max_new_tokens=case["max_new"])
    eng.run_until_drained()
    m0 = plan_cache_info().misses
    a0 = counters()["measure_calls"]
    pc0 = eng.stats["prefill_compiles"]
    eng.submit_audio_stream(samples, max_new_tokens=case["max_new"])
    eng.run_until_drained()
    serving_row = {
        "arch": arch,
        "decode_compiles": int(eng.stats["decode_compiles"]),
        "steady_plan_misses": int(plan_cache_info().misses - m0),
        "steady_measure_calls": int(counters()["measure_calls"] - a0),
        "steady_prefill_compiles": int(eng.stats["prefill_compiles"] - pc0),
        "tokens": len(eng.finished[-1].output),
    }
    print(f"ci-bench stream serving    {arch:13s} "
          f"decode_compiles={serving_row['decode_compiles']} "
          f"steady misses={serving_row['steady_plan_misses']} "
          f"measures={serving_row['steady_measure_calls']} "
          f"prefill_compiles={serving_row['steady_prefill_compiles']}")
    return {"frontend": frontend_row, "first_frame": first_frame_row,
            "serving": serving_row}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    ap.add_argument("--ci", action="store_true",
                    help="bench-regression measurement pass: per-spec "
                         "smoke timings + plan-cache counters as JSON")
    ap.add_argument("--out", default="BENCH_NEW.json",
                    help="output path for --ci (pass "
                         "benchmarks/BENCH_PR10.json explicitly when "
                         "refreshing the committed baseline)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.ci:
        ci_bench(args.out)
        return
    only = args.only.split(",") if args.only != "all" else None

    from benchmarks import (
        bench_kernels,
        bench_mapping,
        bench_recurrences,
        bench_scaling,
        roofline_table,
    )

    sections = {
        "recurrences": bench_recurrences.run,   # Table III
        "mapping": bench_mapping.run,           # Table IV + routing
        "scaling": bench_scaling.run,           # Fig. 6
        "kernels": bench_kernels.run,
        "roofline": roofline_table.run,         # EXPERIMENTS §Roofline
    }
    csv_rows: list = []
    failed = []
    for name, fn in sections.items():
        if only and name not in only:
            continue
        try:
            fn(csv_rows)
        except Exception as e:  # noqa: BLE001 - report every section
            failed.append(name)
            print(f"[bench {name}] FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        sys.exit(f"bench sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
