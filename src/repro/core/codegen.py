"""ExecutionPlan -> executable JAX (paper §IV back half).

Four backends:

  'pallas'  — intra-chip Pallas kernel with the plan's BlockSpec tiles
              (interpret=True on CPU; Mosaic on real TPU).
  'xla'     — plain jnp reference path (used by the 512-device dry-run,
              since Mosaic only lowers for TPU targets).
  'systolic'— chip-level shard_map schedule: the plan's space loops become
              mesh axes; read/flow dependences lower to lax.ppermute
              neighbour streams (the AIE-DMA edge analogue): Cannon rings
              for mm/bmm, a complex two-plane ring for fft2d_stage, width-k
              halo exchange for the jacobi2d stencils, 1-D shifted-window
              chains for conv2d/fir and a staged 2-D ring for mttkrp — the
              full registry.  This is the paper's systolic design at pod
              scale and the baseline for the §Perf collective hillclimb.

There is also 'allgather', the GSPMD broadcast baseline the systolic
schedules are measured against (benchmarks/bench_mapping.py).

Every backend resolves the recurrence through the KernelSpec registry
(``repro/kernels/registry.py``): 'xla' uses the spec's reference lowering,
'pallas' goes through ``runtime.execute_plan``, and the chip-level
backends dispatch through the spec's ``systolic_lowering`` /
``allgather_lowering`` hooks (implemented in ``repro/kernels/systolic.py``)
— codegen carries no per-recurrence schedule of its own.  A spec without
the hook, or a shape its schedule cannot tile over the mesh, raises
``UnsupportedLoweringError``; an unregistered recurrence raises
``registry.UnregisteredRecurrenceError`` from any backend.
"""

from __future__ import annotations

import functools
from typing import Callable

from .mapper import ExecutionPlan


class UnsupportedLoweringError(ValueError):
    """This backend cannot lower this recurrence: no hook is registered,
    or the shape does not tile over the mesh.  The one failure an
    autotune race skips a backend for."""


# ---------------------------------------------------------------------------
# backend: xla (oracle / dry-run path)
# ---------------------------------------------------------------------------

def _xla_fn(plan: ExecutionPlan) -> Callable:
    """The registered reference lowering — one oracle per recurrence,
    shared with the test suite (kernels/ref.py by way of the registry)."""
    return _spec(plan).xla


def _spec(plan: ExecutionPlan):
    # lazy: kernels imports core.partition; codegen must not close the cycle
    from repro.kernels import registry

    return registry.get(plan.recurrence.name)


# ---------------------------------------------------------------------------
# backend: pallas (per-chip kernel with the plan's tiles)
# ---------------------------------------------------------------------------

def _pallas_fn(plan: ExecutionPlan, interpret: bool | None = None) -> Callable:
    """Plan-driven kernel dispatch — the runtime derives block shapes, grid
    and dimension semantics from the plan (see kernels/runtime.py)."""
    from repro.kernels import runtime

    runtime.plan_kernel_kwargs(plan)  # fail fast on unsupported recurrences
    return functools.partial(runtime.execute_plan, plan, interpret=interpret)


# ---------------------------------------------------------------------------
# backend: systolic / allgather (chip-level shard_map schedules)
# ---------------------------------------------------------------------------

def lower_plan(
    plan: ExecutionPlan,
    backend: str = "xla",
    mesh=None,
    interpret: bool | None = None,
) -> Callable:
    from . import fusion  # late: fusion imports mapper imports nothing here
    from . import hierarchy  # late: hierarchy lowers groups through here

    if isinstance(plan, hierarchy.HierarchicalPlan):
        # two-level plans compose the outer split at host/trace level and
        # re-enter lower_plan per group for the inner schedule; the outer
        # composition builds its own per-group meshes, so ``mesh`` is
        # ignored (see core/hierarchy.py: nested shard_map is illegal)
        return hierarchy.lower_hierarchical(
            plan, backend=backend, mesh=mesh, interpret=interpret)
    if isinstance(plan, fusion.FusedPlan):
        # fused chains dispatch through the consumer spec's
        # fused_systolic_lowering hook / the single-launch composition
        # (core/fusion.py) — same backend surface, chain semantics
        return fusion.lower_fused(
            plan, backend=backend, mesh=mesh, interpret=interpret)
    if backend == "xla":
        return _xla_fn(plan)
    if backend == "pallas":
        return _pallas_fn(plan, interpret=interpret)
    if backend in ("systolic", "allgather"):
        assert mesh is not None
        # chip-level schedules are per-recurrence shard_map programs
        # (repro/kernels/systolic.py); every built-in KernelSpec registers
        # both hooks as of PR 5 (Cannon rings, the complex two-plane ring,
        # width-k halo exchange, 1-D chains, the mttkrp ring) — the error
        # below remains for third-party specs that opt out.
        spec = _spec(plan)
        hook = (spec.systolic_lowering if backend == "systolic"
                else spec.allgather_lowering)
        if hook is None:
            raise UnsupportedLoweringError(
                f"{backend} backend: recurrence {spec.name!r} registers no "
                f"{backend} lowering hook (supports_systolic="
                f"{spec.supports_systolic}) — see docs/systolic.md for the "
                "spec-author contract")
        return hook(plan, mesh)
    raise ValueError(f"unknown backend {backend}")
