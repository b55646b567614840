"""Planned-execution bench: model/serve GEMM shapes through the facade.

Two sections:

  * **GEMM shapes** — the dense/attention/decode shapes the model stack
    and serve engine actually emit, timed on the planned path (mapper
    tiles -> execute_plan) vs the XLA reference, with the plan the mapper
    chose.  On CPU the Pallas path runs in interpret mode, so the timing
    is a validity/overhead check, not a TPU number — the interesting
    output is the plan (tiles, utilization) per shape.
  * **Call-site report** — one transformer forward + decode step and a
    2-request serving-engine drain, followed by ``planned_report()``: which
    call sites executed mapper-planned kernels and which fell back.

    PYTHONPATH=src python benchmarks/bench_planned.py [--smoke]
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import planned, ref
from repro.kernels.planned import plan_for, planned_bmm, planned_dense

# (kind, shape, dtype): decode-step projections (M = slots), prefill
# projections (M = B*S), attention scores, an int8 serving quantization row
GEMM_CASES = [
    ("mm", (4, 512, 512), "float32"),      # decode projection, 4 lanes
    ("mm", (512, 2048, 512), "float32"),   # prefill MLP up-projection
    ("mm", (512, 512, 2048), "float32"),   # prefill MLP down-projection
    ("mm", (4, 32000, 512), "float32"),    # decode lm head
    ("mm", (512, 2048, 512), "int8"),      # int8-quantized serving GEMM
    ("bmm", (16, 128, 128, 64), "float32"),  # attention scores, 16 heads
    ("bmm", (16, 128, 64, 128), "float32"),  # attention values
]

SMOKE_SCALE = 8  # divide M/N/K by this under --smoke


def _draw(rng, shape, dtype):
    if dtype.startswith("int"):
        return jnp.asarray(rng.integers(-8, 8, shape).astype(dtype))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _operands(kind, shape, dtype, rng):
    if kind == "mm":
        m, n, k = shape
        return _draw(rng, (m, k), dtype), _draw(rng, (k, n), dtype)
    b, m, n, k = shape
    return _draw(rng, (b, m, k), dtype), _draw(rng, (b, k, n), dtype)


def _timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e3


def bench_gemms(smoke: bool):
    rng = np.random.default_rng(0)
    print(f"{'kind':5} {'shape':>22} {'dtype':>8} {'planned ms':>11} "
          f"{'xla ms':>8}  plan")
    for kind, shape, dtype in GEMM_CASES:
        if smoke:
            shape = tuple(max(1, d // SMOKE_SCALE) for d in shape)
        a, b = _operands(kind, shape, dtype, rng)
        plan = plan_for(kind, shape, dtype)
        f_planned = planned_dense if kind == "mm" else planned_bmm
        f_ref = ref.matmul if kind == "mm" else ref.bmm
        if kind == "mm":
            args = (a.reshape(shape[0], shape[2]), b)
        else:
            args = (a, b)
        out_p, ms_p = _timed(lambda x, w: f_planned(x, w, site="bench"),
                             *args)
        out_r, ms_r = _timed(f_ref, *args)
        np.testing.assert_allclose(
            np.asarray(out_p, np.float32), np.asarray(out_r, np.float32),
            atol=1e-2, rtol=1e-3)
        desc = plan.partition.describe() if plan is not None else "fallback"
        print(f"{kind:5} {str(shape):>22} {dtype:>8} {ms_p:>11.2f} "
              f"{ms_r:>8.2f}  {desc}")


def report_model_sites():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import make_engine

    cfg = get_smoke_config("qwen1.5-0.5b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    planned.planned_report_clear()
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    api.loss(params, {"tokens": toks, "labels": toks})

    eng = make_engine(cfg, max_lanes=2, max_seq=32, block_size=8)
    eng.load(params)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab, 6), max_new_tokens=4)
    eng.run_until_drained()

    print("\ncall-site report (forward + serve drain):")
    for site, st in planned.planned_report().items():
        if "/bwd_" in site or site == "bench":
            continue
        tail = f" reasons={st['reasons']}" if st["fallback"] else ""
        print(f"  {site:20} planned={st['planned']:3} "
              f"fallback={st['fallback']:3}{tail}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for the CI gate")
    args = ap.parse_args()
    bench_gemms(args.smoke)
    report_model_sites()
    print("OK")


if __name__ == "__main__":
    main()
