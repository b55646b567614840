"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 16 --max-new 8 [--published] \
        [--prompt-min 4 --prompt-max 15] [--seed 0] [--stream-audio]

The architecture's ``SMOKE`` preset is served unless ``--published``
asks for its published configuration (full widths; on one chip, not on
a CPU).  Weights are random, made on the device from ``--seed``.

``--stream-audio`` (encdec archs) submits synthesized raw-audio
requests that stream through the planned frontend chunk by chunk —
the CI smoke for chunked admission, pinning ``decode_compiles == 1``
and ``measure_calls == 0`` while streaming.

``chip_smoke.py`` serves through the same functions.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax


def load_engine(cfg, *, lanes: int = 4, max_seq: int = 128,
                block_size: int = 16, seed: int = 0):
    """Build and ``load()`` the serving engine for ``cfg`` with random
    weights made on the device from ``seed``.  Returns (engine, params)."""
    from repro.serve import make_engine

    eng = make_engine(cfg, max_lanes=lanes, max_seq=max_seq,
                      block_size=block_size)
    params = jax.jit(eng.api.init)(jax.random.PRNGKey(seed))
    eng.load(params)
    return eng, params


def text_prompts(cfg, n: int, lo: int, hi: int, seed: int = 0) -> list:
    """``n`` random token prompts with lengths uniform in [lo, hi]."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, n)
    return [rng.integers(0, cfg.vocab, int(n_tok), dtype=np.int32)
            for n_tok in lens]


def site_rows(report: dict) -> list:
    """Forward call sites of a ``planned_report``: (site, planned
    traces, fallback traces, executed backends, fallback reasons,
    autotune hit/miss)."""
    return [(site, st["planned"], st["fallback"], st["backends"],
             st["reasons"], st["autotune"])
            for site, st in report.items() if "/bwd_" not in site]


def print_sites(rows: list) -> None:
    print("planned GEMM call sites (site: planned/fallback traces, "
          "executed backends, fallback reasons, autotune hit/miss):")
    for site, n_planned, n_fallback, backends, reasons, tune in rows:
        mix = ",".join(f"{b}={n}" for b, n in sorted(backends.items()))
        why = ",".join(f"{r}={n}" for r, n in sorted(reasons.items()))
        print(f"  {site}: {n_planned}/{n_fallback}  [{mix or '-'}]  "
              f"{{{why}}}  tune {tune['hit']}/{tune['miss']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--published", action="store_true",
                    help="serve the published config, not SMOKE")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode lanes")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block granularity")
    ap.add_argument("--stream-audio", action="store_true",
                    help="submit synthesized audio streams through the "
                         "planned frontend (encdec archs only)")
    args = ap.parse_args()

    from repro.configs import get_config, get_smoke_config
    from repro.kernels import planned_report
    from repro.kernels.planned import planned_enabled
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import synth_samples

    enable_compile_cache()
    cfg = (get_config if args.published else get_smoke_config)(args.arch)
    eng, _ = load_engine(cfg, lanes=args.slots, max_seq=args.max_seq,
                         block_size=args.block_size, seed=args.seed)

    if args.stream_audio and eng.frontend is None:
        raise SystemExit(
            f"--stream-audio needs an encdec arch; {args.arch} has no "
            "audio frontend")

    rng = np.random.default_rng(args.seed)
    prompts = text_prompts(cfg, args.requests, args.prompt_min,
                           args.prompt_max, seed=args.seed)
    for i, prompt in enumerate(prompts):
        if args.stream_audio:
            n_chunks = 1 + i % (cfg.enc_frames
                                // eng.frontend.cfg.frames_per_chunk)
            eng.submit_audio_stream(
                synth_samples(eng.frontend.cfg, n_chunks, seed=i),
                max_new_tokens=args.max_new)
            continue
        extra = None
        if cfg.family == "encdec":  # audio models decode against frames
            extra = {"frames": np.asarray(jax.numpy.asarray(
                rng.standard_normal((cfg.enc_frames, cfg.d_model)),
                jax.numpy.bfloat16))}
        eng.submit_text(prompt, max_new_tokens=args.max_new, extra=extra)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) on {jax.devices()[0].platform}")

    rows = site_rows(planned_report())
    print_sites(rows)
    print(f"autotune (load-time delta): {eng.autotune_report}")
    print(f"paged stats: {eng.stats}")
    assert eng.stats["decode_compiles"] == 1, \
        "in-flight traffic recompiled the AOT decode executable"
    if args.stream_audio:
        # the streaming invariants CI pins: chunk feeds never touch the
        # decode executable, and the frontend's planned stages ran
        front = [row[0] for row in rows
                 if row[0].startswith("frontend.") and row[1]]
        assert front, "audio streaming executed no planned frontend stages"
        print(f"planned frontend stages: {sorted(front)}")
    if planned_enabled():
        assert any(row[1] for row in rows), \
            "serving executed no planned GEMMs"
        assert eng.autotune_report.get("measure_calls", 0) == 0, \
            "serve-time planning must not measure"


if __name__ == "__main__":
    main()
