"""Serving load generator: Poisson arrivals against a serve engine,
reporting tokens/sec, p50/p99 request latency, and preemption /
recompile counts.

The clock is *virtual*: arrival times come from a seeded exponential
inter-arrival draw, and the clock advances by the measured wall time of
each ``engine.step()``.  When the engine is fully idle (no active lanes,
empty queue) the clock jumps to the next arrival instead of spinning.
This keeps the workload deterministic (same seed -> same arrival
pattern and prompt lengths -> same admission order) while the timings
remain real measurements of the engine's step cost.

    PYTHONPATH=src python -m benchmarks.bench_serving \
        --arch qwen1.5-0.5b --rates 2,8 --requests 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _occupied(engine) -> int:
    return sum(r is not None for r in engine.lanes)


def make_requests(cfg, n: int, *, seed: int, prompt_lens=(4, 20),
                  max_new: int = 4):
    """Deterministic request set: seeded prompt lengths and token ids."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [
        (rng.integers(0, cfg.vocab,
                      int(rng.integers(lo, hi + 1))).astype(np.int32),
         max_new)
        for _ in range(n)
    ]


def run_load(engine, requests, *, rate: float, seed: int = 0) -> dict:
    """Drive ``requests`` through ``engine`` at Poisson ``rate`` (req/s,
    virtual time).  The engine must already be loaded; its prior
    ``finished`` history is left untouched (measurement starts from the
    current offset, so a warmup pass on the same engine is fine)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, len(requests)))
    done_offset = len(engine.finished)
    stats0 = dict(engine.stats)
    now = 0.0
    submitted = 0
    submit_time: dict = {}
    finish_time: dict = {}
    steps = 0
    while len(engine.finished) - done_offset < len(requests):
        while (submitted < len(requests)
               and arrivals[submitted] <= now):
            prompt, max_new = requests[submitted]
            rid = engine.submit(prompt, max_new_tokens=max_new)
            submit_time[rid] = arrivals[submitted]
            submitted += 1
        if (_occupied(engine) == 0 and not engine.queue
                and submitted < len(requests)):
            now = float(arrivals[submitted])
            continue
        t0 = time.perf_counter()
        engine.step()
        now += time.perf_counter() - t0
        steps += 1
        for r in engine.finished[done_offset:]:
            finish_time.setdefault(r.rid, now)
    lat = np.asarray([
        finish_time[r.rid] - submit_time[r.rid]
        for r in engine.finished[done_offset:]])
    tokens = sum(len(r.output) for r in engine.finished[done_offset:])
    makespan = max(now, 1e-9)
    stats1 = dict(engine.stats)
    return {
        "rate": rate,
        "requests": len(requests),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / makespan, 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 1),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 1),
        "steps": steps,
        "preemptions": stats1["preemptions"] - stats0["preemptions"],
        # compiles after load() == in-flight recompiles; the engine's
        # AOT invariant pins this at 0
        "decode_recompiles": (stats1["decode_compiles"]
                              - stats0["decode_compiles"]),
    }


def warmup(engine, cfg, *, seed: int = 99, max_new: int = 2,
           prompt_lens=(4, 20)) -> None:
    """Touch every prefill bucket the measured pass will hit, so jit
    compilation happens outside the timed window (steady-state measure,
    the same contract the kernel benches use)."""
    lo, hi = prompt_lens
    lens = {engine.scheduler.bucket_for(n, exact=engine._exact_prefill)
            for n in range(lo, hi + 1)}
    lens = {min(n, engine.max_seq - max_new) for n in lens}
    rng = np.random.default_rng(seed)
    for n in sorted(lens):
        engine.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                      max_new_tokens=max_new)
    engine.run_until_drained()


def sweep(engine, cfg, rates, *, requests: int = 16, seed: int = 0,
          prompt_lens=(4, 20), max_new: int = 4) -> list[dict]:
    warmup(engine, cfg, prompt_lens=prompt_lens, max_new=max_new)
    rows = []
    for rate in rates:
        reqs = make_requests(cfg, requests, seed=seed,
                             prompt_lens=prompt_lens, max_new=max_new)
        rows.append(run_load(engine, reqs, rate=rate, seed=seed))
    return rows


def build_engine(arch: str, *, max_lanes: int = 4,
                 max_seq: int = 64, block_size: int = 8,
                 num_blocks: int | None = None, seed: int = 42):
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import make_engine

    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    eng = make_engine(cfg, max_lanes=max_lanes, max_seq=max_seq,
                      block_size=block_size, num_blocks=num_blocks)
    eng.load(params)
    return cfg, eng


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--rates", default="2,8",
                    help="comma-separated Poisson arrival rates (req/s)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cfg, eng = build_engine(args.arch, max_lanes=args.lanes,
                            max_seq=args.max_seq,
                            block_size=args.block_size,
                            num_blocks=args.num_blocks)
    rates = [float(r) for r in args.rates.split(",")]
    rows = sweep(eng, cfg, rates, requests=args.requests, seed=args.seed,
                 max_new=args.max_new)
    print(f"{'rate':>8} {'tok/s':>10} {'p50_ms':>10} {'p99_ms':>10} "
          f"{'preempt':>8} {'recompile':>9}")
    for row in rows:
        print(f"{row['rate']:8.1f} {row['tokens_per_sec']:10.2f} "
              f"{row['p50_ms']:10.1f} {row['p99_ms']:10.1f} "
              f"{row['preemptions']:8d} {row['decode_recompiles']:9d}")


if __name__ == "__main__":
    main()
