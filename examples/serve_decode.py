"""Serving example: continuous batching over mixed-length requests.

Trains nothing — loads random weights into the serving engine and drives
batched prefill + decode with requests arriving mid-flight, for two
architectures (dense + SSM) to show the cache-agnostic engine.

    PYTHONPATH=src python examples/serve_decode.py
"""

import time

import numpy as np
import jax

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import make_engine


def drive(arch: str):
    cfg = get_smoke_config(arch)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = make_engine(cfg, max_lanes=4, max_seq=64, block_size=8)
    eng.load(params)
    rng = np.random.default_rng(0)

    # 6 requests with different lengths; 3 arrive later (continuous batching)
    for i in range(3):
        eng.submit(rng.integers(0, cfg.vocab, 4 + 3 * i),
                   max_new_tokens=6 + i)
    t0 = time.perf_counter()
    steps = 0
    late_submitted = False
    while True:
        remaining = eng.step()
        steps += 1
        if steps == 2 and not late_submitted:
            for i in range(3):
                eng.submit(rng.integers(0, cfg.vocab, 5), max_new_tokens=5)
            late_submitted = True
        if remaining == 0:
            break
    dt = time.perf_counter() - t0
    done = eng.finished
    tokens = sum(len(r.output) for r in done)
    print(f"  {arch}: {len(done)} requests, {tokens} tokens, "
          f"{steps} engine steps, {dt*1e3:.0f} ms "
          f"({tokens/dt:.0f} tok/s on CPU)")
    assert len(done) == 6
    for r in done:
        assert len(r.output) >= 5


def main():
    print("continuous-batching decode (random weights, greedy):")
    drive("qwen1.5-0.5b")
    drive("mamba2-780m")
    drive("zamba2-1.2b")
    print("OK")


if __name__ == "__main__":
    main()
