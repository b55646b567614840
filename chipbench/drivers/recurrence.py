"""Driver kind ``recurrence``: a closed loop of calls of one planned
recurrence, each ended by ``block_until_ready``.

Cell settings (``chipbench/workloads/<cell>.json``):

  mesh            [rows, cols] of the plan's Target; [1, 1] is one chip
  backend         "pallas" (``runtime.execute_plan``) or "systolic"
                  (``lower_plan`` on a mesh of the cell's chips)
  operand_sets    distinct (A, B) pairs the calls rotate through
  check_calls     calls whose answers are kept and compared, drawn from
                  the seed among the first ``check_from`` calls; the last
                  call's answer is always compared too
  trace_seconds   length of the traced part of the window
  limit           the comparison's limit (see PERF.md)

Configuration (``chipbench/configs/<config>.json``): ``recurrence``
("mm"), ``extents`` {i, j, k}, ``dtype`` and ``precision``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, trace, traffic, work
from chipbench.reference import mm as ref


def _program(cfg, settings, devs):
    """(compiled call, operand shardings or None) of the system under
    test, traced under the precision the configuration states."""
    from repro.core import Target, best_plan, lower_plan
    from repro.core.recurrence import matmul
    from repro.kernels import runtime

    ext = cfg["extents"]
    rec = matmul(ext["i"], ext["j"], ext["k"], cfg["dtype"])
    mesh_shape = tuple(settings["mesh"])
    target = Target(name=f"chip_{mesh_shape[0]}x{mesh_shape[1]}",
                    mesh_shape=mesh_shape)
    plan = best_plan(rec, target)
    harness.log(f"plan: mesh {mesh_shape}, blocks {plan.partition.block}, "
                f"backend {settings['backend']}")
    shapes = [jax.ShapeDtypeStruct((ext["i"], ext["k"]), cfg["dtype"]),
              jax.ShapeDtypeStruct((ext["k"], ext["j"]), cfg["dtype"])]
    with jax.default_matmul_precision(cfg["precision"]):
        if settings["backend"] == "pallas":
            fn = jax.jit(lambda a, b: runtime.execute_plan(plan, a, b))
            return fn.lower(*shapes).compile(), None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.compat import make_mesh

        mesh = make_mesh(target.mesh_shape, target.mesh_axes, devices=devs)
        # Cannon's layout: A (i, k) and B (k, j) over the two mesh axes,
        # C (i, j) likewise; the output stays sharded
        sh = NamedSharding(mesh, P(*target.mesh_axes))
        body = lower_plan(plan, backend=settings["backend"], mesh=mesh)
        fn = jax.jit(body, in_shardings=(sh, sh), out_shardings=sh)
        shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
                  for s in shapes]
        return fn.lower(*shapes).compile(), sh


def _operands(cfg, n_sets: int, seed: int, sharding):
    """``n_sets`` (A, B) pairs of standard normal entries, made on the
    device in one jitted call from the seed."""
    ext = cfg["extents"]
    key = jax.random.PRNGKey(int(traffic.rng_for(seed, "operands")
                                 .integers(0, 2**31)))

    def make(key):
        out = []
        for ka, kb in jax.random.split(key, (n_sets, 2)):
            out.append((
                jax.random.normal(ka, (ext["i"], ext["k"])).astype(
                    cfg["dtype"]),
                jax.random.normal(kb, (ext["k"], ext["j"])).astype(
                    cfg["dtype"])))
        return out

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)(key)


def run(ctx: harness.Context) -> harness.Run:
    cell, cfg, settings = ctx.cell, ctx.cell.config, ctx.cell.settings
    devs = ctx.devices
    ext = cfg["extents"]
    ops = work.mm_ops(ext["i"], ext["j"], ext["k"])

    prog, sharding = _program(cfg, settings, devs)
    pairs = _operands(cfg, settings["operand_sets"], ctx.seed, sharding)
    jax.block_until_ready(pairs)
    prog(*pairs[0]).block_until_ready()         # warm: first execution
    counter = harness.CompileCounter()
    before = counter.snapshot()

    rng = traffic.rng_for(ctx.seed, "check_calls")
    sample = set(rng.choice(settings["check_from"], settings["check_calls"],
                            replace=False).tolist())
    spans = harness.Spans()
    tracer = trace.Session(ctx.trace, spans, keep=ctx.keep_trace)
    kept, ends = {}, []
    t0 = harness.now()
    setup_s = t0 - ctx.t_start
    deadline = t0 + ctx.seconds
    tracer.start(until=t0 + min(ctx.seconds, settings["trace_seconds"]))
    n = 0
    while True:
        i = n % len(pairs)
        with spans.span("call"):
            out = prog(*pairs[i])
            out.block_until_ready()
        t = harness.now()
        ends.append(t)
        if n in sample:
            kept[n] = (i, out)
        n += 1
        tracer.maybe_stop(t)
        if t >= deadline:
            break
    kept[n - 1] = (i, out)
    window_s = ends[-1] - t0
    reduced = tracer.stop(devs)
    after = counter.snapshot()
    harness.log(f"window: {n} calls in {window_s:.3f}s; programs traced/"
                f"compiled before {before}, after {after}")
    mem = harness.memory_peak_bytes(devs)
    del out

    errs = []
    for call, (i, got) in sorted(kept.items()):
        a, b = pairs[i]
        errs.append(ref.rel_err(got, ref.matmul(a, b, cfg["precision"])))
        harness.log(f"check call {call} (operand set {i}): "
                    f"max_rel_err {errs[-1]!r}")
    checks = [harness.Check("mm_max_rel_err", float(np.max(errs)),
                            settings["limit"])]
    traced_calls = (sum(1 for t in ends if t <= tracer.stopped_at)
                    if reduced is not None else 0)
    return harness.Run(
        setup_s=setup_s, window_s=window_s, attempted=n, failed=0,
        checks=checks, memory_peak_bytes=mem, peaks=ctx.peaks, chips=len(devs),
        trace=reduced, data={
            "calls": n, "ops_per_call": ops,
            "bytes_per_call": work.mm_bytes(
                ext["i"], ext["j"], ext["k"],
                jnp.dtype(cfg["dtype"]).itemsize),
            "traced_calls": traced_calls,
        })
