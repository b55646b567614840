"""Bring-up check on the chip: the paper's kernels and one served model.

    python chip_smoke.py [--seed 0]        # one TPU chip
    python chip_smoke.py --chips 4         # the 2x2 chip-level schedules

On one chip the phases run in order, and any failure exits non-zero:

  (a) device   — JAX must report TPU devices; there is no CPU fallback.
  (b) kernels  — every registered recurrence x bench dtype: planned by
                 ``best_plan`` for one chip, executed by ``execute_plan``
                 through Mosaic (interpret mode off) at its bench size,
                 against the registry's XLA lowering on the same chip.
                 A case whose programs do not fit the chip's memory is
                 cut in half on its first builder extent until they do,
                 and the cut is printed.  Integers must match exactly;
                 floats within ``FLOAT_RTOL`` of the largest reference
                 magnitude (reference at HIGHEST matmul precision, and
                 the kernels' float32 dots run at HIGHEST too).
  (c) serving  — qwen1.5-0.5b at its published config (24L, d=1024,
                 vocab 151936, bf16) with random weights from ``--seed``:
                 the paged engine (4 lanes, max_seq 2048, block 16)
                 serves 8 requests of 64-512 prompt tokens and 32 new
                 tokens each, through ``repro.launch.serve``.  The AOT
                 decode must compile once, Pallas must be among the
                 executed backends, and request 0's prefill logits must
                 agree with the facade-disabled model within
                 ``LOGITS_RTOL``.
  (d) the last line of stdout: ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the chip-level schedules: for every spec with
systolic/allgather hooks, ``lower_plan`` on a 2x2 mesh of the four chips
(first bench case, cut to fit) against the XLA reference, each output
laid out on 4 distinct devices.

Data and weights are made on the device from ``--seed``.  The compile
cache follows ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

#: Float kernels vs the XLA reference: max |out - ref| / max |ref|.
#: Both sides accumulate float32 at full precision; what is left is the
#: summation order over reductions up to 10^4 long.
FLOAT_RTOL = 1e-3
#: Planned (Pallas) vs facade-disabled (XLA) prefill logits, bf16 model:
#: the two differ only in float32 accumulation order, which flips a bf16
#: activation by one ulp (2**-8 relative) now and then; 24 layers compound
#: a few such flips.
LOGITS_RTOL = 5e-2
#: Share of the chip's memory a kernel case may plan for.
MEMORY_SHARE = 0.8

SERVE_ARCH = "qwen1.5-0.5b"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _footprint(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.output_size_in_bytes + ma.temp_size_in_bytes


def _rel_err(out, want) -> float:
    """max |out - want| / max |want| over every leaf (floats)."""
    num = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)))
    den = max(float(jnp.max(jnp.abs(b.astype(jnp.float32))))
              for b in jax.tree.leaves(want))
    return num / max(den, 1e-30)


def compare(out, want) -> tuple[str, bool]:
    """(message, ok): exact for integers, ``FLOAT_RTOL`` for floats."""
    outs, wants = jax.tree.leaves(out), jax.tree.leaves(want)
    if [(a.shape, a.dtype) for a in outs] != [(b.shape, b.dtype)
                                              for b in wants]:
        return (f"shape/dtype {[(a.shape, a.dtype) for a in outs]} != "
                f"{[(b.shape, b.dtype) for b in wants]}", False)
    if jnp.issubdtype(wants[0].dtype, jnp.integer):
        bad = sum(int(jnp.sum(a != b)) for a, b in zip(outs, wants))
        return f"mismatches {bad} (exact)", bad == 0
    finite = all(bool(jnp.all(jnp.isfinite(a))) for a in outs)
    err = _rel_err(outs, wants)
    return (f"max_rel_err {err:.3e} (tol {FLOAT_RTOL:.0e})",
            finite and err <= FLOAT_RTOL)


def fit_case(spec, args: tuple, build, budget: int):
    """Halve the first builder extent until ``build(args)``'s programs
    fit ``budget`` bytes.  ``build`` returns (operand shapes, compiled
    programs, compile seconds of the first).  Returns (args, build
    result, original args if cut else None)."""
    orig = args
    while True:
        built = build(args)
        need = _nbytes(built[0]) + sum(_footprint(c) for c in built[1])
        if need <= budget:
            return args, built, (orig if args != orig else None)
        if args[0] < 2:
            raise RuntimeError(
                f"{spec.name} {orig}: needs {need / 2**30:.2f} GiB even "
                "at its smallest cut")
        args = (args[0] // 2, *args[1:])


def device_budget(device) -> int:
    stats = device.memory_stats()
    return int(stats["bytes_limit"] * MEMORY_SHARE)


# ---------------------------------------------------------------------------
# (b) kernels on one chip
# ---------------------------------------------------------------------------

def kernel_case(spec, dtype: str, args: tuple, *, key, budget: int,
                target) -> bool:
    """One registered recurrence through execute_plan vs its XLA lowering."""
    from repro.core import best_plan
    from repro.core.autotune import EXEC_DTYPE
    from repro.kernels import execute_plan
    from repro.kernels.registry import DeviceRng

    exec_dtype = EXEC_DTYPE.get(dtype, dtype)

    def build(a):
        rec = spec.builder(*a, exec_dtype)
        plan = best_plan(rec, target)
        make = jax.jit(lambda k: spec.operands(rec, DeviceRng(k)))
        shapes = jax.eval_shape(make, key)
        t0 = time.perf_counter()
        kern = jax.jit(lambda *o: execute_plan(plan, *o)).lower(
            *shapes).compile()
        secs = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(spec.xla).lower(*shapes).compile()
        return shapes, (kern, ref), secs, make, plan

    args, (_, (kern, ref), secs, make, plan), cut = fit_case(
        spec, tuple(args), build, budget)
    ops = make(key)
    msg, ok = compare(kern(*ops), ref(*ops))
    del ops
    cut_msg = f" (cut from {cut} to fit)" if cut else ""
    print(f"kernel {spec.name:13s} {dtype:7s} {args}{cut_msg} "
          f"blocks={plan.partition.block} compile {secs:.2f}s {msg} "
          f"{'ok' if ok else 'FAIL'}", flush=True)
    return ok


def kernel_phase(key, budget: int) -> bool:
    from repro.core import Target
    from repro.kernels import registry, runtime

    if runtime.resolve_interpret(None):
        raise RuntimeError("Pallas kernels would run in interpret mode")
    target = Target(name="single_chip", mesh_shape=(1, 1))
    cases = [(spec, dtype, args) for spec in registry.specs()
             for dtype, args in spec.bench_cases]
    ok = True
    for i, (spec, dtype, args) in enumerate(cases):
        ok &= kernel_case(spec, dtype, args, key=jax.random.fold_in(key, i),
                          budget=budget, target=target)
    return ok


# ---------------------------------------------------------------------------
# (c) serving at published width
# ---------------------------------------------------------------------------

def prefill_logits(eng, params, prompt):
    """Last-position prefill logits of one prompt, float32, traced under
    whatever ``planned`` configuration is current."""
    n = len(prompt)
    fn = jax.jit(lambda p, t, li: eng.api.prefill(
        p, {"tokens": t}, n, last_index=li)[0])
    out = fn(params, jnp.asarray(prompt)[None],
             jnp.asarray([n - 1], jnp.int32))
    return out.astype(jnp.float32)


def serving_phase(cfg, *, seed: int, lanes: int = 4, max_seq: int = 2048,
                  block_size: int = 16, requests: int = 8,
                  prompt_lens: tuple[int, int] = (64, 512),
                  max_new: int = 32) -> bool:
    from repro.kernels import planned
    from repro.launch import serve

    t0 = time.perf_counter()
    eng, params = serve.load_engine(
        cfg, lanes=lanes, max_seq=max_seq, block_size=block_size,
        seed=seed)
    print(f"serve {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab} {cfg.dtype}; loaded in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    prompts = serve.text_prompts(cfg, requests, *prompt_lens, seed=seed)
    for p in prompts:
        eng.submit_text(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    print(f"served {len(done)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, "
          f"{sum(len(r.output) for r in done)} tokens in "
          f"{time.perf_counter() - t0:.1f}s (compiles included); "
          f"stats {eng.stats}", flush=True)
    rows = serve.site_rows(planned.planned_report())
    serve.print_sites(rows)
    backends = {b for row in rows for b in row[3]}
    ok = True
    checks = [
        (len(done) == requests
         and all(len(r.output) == max_new for r in done),
         f"{requests} requests x {max_new} tokens"),
        (all(0 <= t < cfg.vocab for r in done for t in r.output),
         "token ids inside the vocabulary"),
        (eng.stats["decode_compiles"] == 1, "decode_compiles == 1"),
        ("pallas" in backends, f"pallas among executed backends {backends}"),
    ]
    got = prefill_logits(eng, params, prompts[0])
    with planned.override(enabled=False):
        want = prefill_logits(eng, params, prompts[0])
    err = _rel_err(got, want)
    same_top = bool(jnp.all(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    checks.append((bool(jnp.all(jnp.isfinite(got))) and err <= LOGITS_RTOL,
                   f"request 0 prefill logits vs facade disabled: "
                   f"max_rel_err {err:.3e} (tol {LOGITS_RTOL:.0e}), "
                   f"same argmax {same_top}"))
    for passed, what in checks:
        print(f"serve check: {what}: {'ok' if passed else 'FAIL'}")
        ok &= passed
    return ok


# ---------------------------------------------------------------------------
# --chips 4: the chip-level schedules on a 2x2 mesh
# ---------------------------------------------------------------------------

def mesh_phase(key, budget: int, devices) -> bool:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.core import Target, best_plan, lower_plan
    from repro.core.autotune import EXEC_DTYPE
    from repro.kernels import registry
    from repro.kernels.registry import DeviceRng

    target = Target(name="chip_2x2", mesh_shape=(2, 2))
    mesh = make_mesh(target.mesh_shape, target.mesh_axes, devices=devices)
    replicated = NamedSharding(mesh, P())
    ok = True
    for spec in registry.specs():
        if spec.systolic_lowering is None or spec.allgather_lowering is None:
            continue
        dtype, args = spec.bench_cases[0]
        exec_dtype = EXEC_DTYPE.get(dtype, dtype)

        def build(a, spec=spec, exec_dtype=exec_dtype):
            rec = spec.builder(*a, exec_dtype)
            plan = best_plan(rec, target)
            make = jax.jit(lambda k: spec.operands(rec, DeviceRng(k)),
                           out_shardings=replicated)
            shapes = jax.eval_shape(make, key)
            shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=replicated), shapes)
            progs = []
            t0 = time.perf_counter()
            with jax.default_matmul_precision("highest"):
                for backend in ("systolic", "allgather"):
                    fn = lower_plan(plan, backend=backend, mesh=mesh)
                    progs.append(jax.jit(fn).lower(*shapes).compile())
                progs.append(jax.jit(spec.xla).lower(*shapes).compile())
            return shapes, progs, time.perf_counter() - t0, make

        args, (_, progs, secs, make), cut = fit_case(
            spec, tuple(args), build, budget)
        ops = make(key)
        want = progs[2](*ops)
        cut_msg = f" (cut from {cut} to fit)" if cut else ""
        for backend, prog in zip(("systolic", "allgather"), progs):
            out = prog(*ops)
            n_dev = min(len(leaf.sharding.device_set)
                        for leaf in jax.tree.leaves(out))
            msg, case_ok = compare(out, want)
            case_ok &= n_dev == 4
            print(f"mesh2x2 {spec.name:13s} {dtype:7s} {args}{cut_msg} "
                  f"{backend:9s} on {n_dev} devices {msg} "
                  f"{'ok' if case_ok else 'FAIL'}", flush=True)
            ok &= case_ok
        print(f"mesh2x2 {spec.name} compile {secs:.2f}s (both backends "
              "and the reference)", flush=True)
        del ops, want
    return ok


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the chip-level schedules on a "
                         "2x2 mesh of four chips")
    args = ap.parse_args()

    # (a) device: a TPU or nothing
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    key = jax.random.PRNGKey(args.seed)
    budget = device_budget(dev)
    t0 = time.perf_counter()
    if args.chips == 4:
        ok = mesh_phase(key, budget, devices[:4])
    else:
        ok = kernel_phase(key, budget)
        print(f"kernels: {'ok' if ok else 'FAIL'} "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)
        ok &= serving_phase(get_config(SERVE_ARCH), seed=args.seed)
    print(f"all phases: {'ok' if ok else 'FAIL'} "
          f"({time.perf_counter() - t0:.0f}s)", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
