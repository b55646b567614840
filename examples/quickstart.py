"""Quickstart: map a uniform recurrence with WideSA and execute it.

Runs the full paper pipeline on a small MM:
  recurrence -> space-time schedules -> partition -> PLIO assignment ->
  ExecutionPlan -> Pallas kernel execution (interpret mode on CPU).

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import (
    AIE_TARGET,
    Target,
    best_plan,
    enumerate_schedules,
    lower_plan,
    map_recurrence,
    matmul,
)
from repro.kernels import execute_plan, registry


def main():
    rec = matmul(1024, 1024, 1024, "float32")
    print(f"recurrence: {rec.name} loops={rec.loops} extents={rec.extents}")
    print("dependences:")
    for d in rec.dependences():
        print(f"  {d.array:3s} {d.kind:7s} distance={d.distance}")

    print("\nlegal systolic schedules (paper §III-B1):")
    for s in enumerate_schedules(rec):
        print(f"  {s.describe()}")

    print("\ntop plans on the VCK5000 AIE target (8x50):")
    for p in map_recurrence(rec, AIE_TARGET, top_k=3):
        print(f"  {p.describe()}")

    print("\ntop plan on the TPU pod target (16x16):")
    plan = best_plan(rec, Target())
    print(f"  {plan.describe()}")
    print(f"  PLIO->column assignment (first 8): "
          f"{dict(list(plan.plio_assignment.items())[:8])}")
    print(f"  collective axis per stream: "
          f"{plan.axis_assignment.stream_axis}")

    print("\nexecuting the plan (Pallas; the interpreter off a TPU):")
    fn = lower_plan(plan, backend="pallas")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.float32)
    out = fn(a, b)
    err = float(jnp.max(jnp.abs(out - a @ b)))
    print(f"  max |pallas - jnp| = {err:.2e}")
    assert err < 1e-2

    print("\nregistered recurrences (kernels/registry.py):")
    for name in registry.registered_names():
        spec = registry.get(name)
        print(f"  {name:12s} arity={spec.arity} grid={spec.grid_loops} "
              f"systolic={spec.supports_systolic}")

    print("\nany registered recurrence runs the same way — MTTKRP:")
    spec = registry.get("mttkrp")
    rec = spec.builder(64, 48, 16, 8, "float32")
    plan = best_plan(rec, Target(name="single_chip", mesh_shape=(1, 1)))
    operands = spec.operands(rec, rng)
    out = execute_plan(plan, *operands)
    err = float(jnp.max(jnp.abs(out - spec.xla(*operands))))
    print(f"  {plan.describe()}")
    print(f"  max |pallas - xla| = {err:.2e}")
    assert err < 1e-2
    print("OK")


if __name__ == "__main__":
    main()
