"""Pallas TPU kernels for the paper's compute hot-spots.

    registry.py   — KernelSpec registry: the per-recurrence execution
                    contract (arity, grid loops, tile kwargs, Pallas +
                    XLA lowerings, capabilities) in one place
    runtime.py    — plan-driven runtime: the helpers every kernel shares
                    + execute_plan(plan, *operands) registry dispatch
    systolic.py   — chip-level shard_map schedules (Cannon rings for
                    mm/bmm, halo exchange for the jacobi2d stencils, and
                    the all-gather baselines) — the KernelSpec
                    systolic_lowering/allgather_lowering hook targets
    widesa_mm.py  — systolic MM (the paper's flagship benchmark)
    bmm.py        — batched MM (the model-stack shape)
    conv2d.py     — 2-D conv as stacked-window MM recurrence
    fir.py        — FIR as stacked-window MM recurrence
    fft2d.py      — 2-D FFT as four-step matmul stages (MXU-native)
    jacobi2d.py   — 5-point stencil kernel (single grid visit per tile;
                    ops.jacobi2d_ms loops it over sweeps)
    mttkrp.py     — MTTKRP (tensor-decomposition hot loop)
    ops.py        — jit'd public wrappers (staging layer / DMA analogue)
    planned.py    — planned-execution facade: planned_dense/planned_bmm
                    route model & serving GEMMs through best_plan ->
                    execute_plan with an XLA fallback + per-site report
    ref.py        — pure-jnp oracles (= the registry's XLA lowerings)

Off a TPU the kernels run in the Pallas interpreter; on a TPU they
compile through Mosaic (``runtime.resolve_interpret``), with blocks kept
Mosaic-legal by the staging layer (``runtime.tile``).  Adding a
kernel = an IR builder in core/recurrence.py + one registry entry (README:
'Adding a new recurrence').
"""

from . import ops, planned, ref, registry, runtime
from .planned import (
    planned_bmm,
    planned_dense,
    planned_report,
    planned_report_clear,
)
from .registry import KernelSpec, UnregisteredRecurrenceError
from .runtime import execute_plan

__all__ = [
    "ops", "planned", "ref", "registry", "runtime",
    "KernelSpec", "UnregisteredRecurrenceError", "execute_plan",
    "planned_dense", "planned_bmm", "planned_report",
    "planned_report_clear",
]
