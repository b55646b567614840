"""Plan-driven Pallas kernel runtime (the ExecutionPlan -> kernel contract).

Two jobs:

1. **What every kernel shares.**  ``compiler_params(...)`` builds the
   Pallas TPU compiler params (an unknown kwarg is an error);
   ``resolve_interpret`` decides interpret mode from the backend alone
   (Mosaic compiles the kernels on a TPU; everywhere else they run in the
   Pallas interpreter, and on a TPU the interpreter is refused);
   ``tile`` makes a requested block extent Mosaic-legal; ``mxu_dot`` is
   the one block contraction every MXU kernel uses, with the integer
   paths the MXU takes.  The dtype packing ladder is shared with
   ``core/partition`` (one source of truth for DTYPE_BYTES/PACKING
   between the cost model and the runtime).

2. **``execute_plan(plan, *operands)``.**  A single entry point that takes
   a ``mapper.ExecutionPlan``, looks up the recurrence's ``KernelSpec`` in
   ``kernels/registry.py``, and invokes its Pallas lowering with block
   shapes, grid and dimension semantics derived *from the plan* — the
   per-kernel tile heuristics live in the mapper's partition search, and
   the per-recurrence contract (arity, grid loops, tile kwargs) lives in
   the registry, not in call sites.

Codegen's pallas backend, ops-level callers and the benchmarks all route
through this module, which makes the mapper's ExecutionPlan the executable
contract rather than a planning artifact.  An unregistered recurrence
raises ``registry.UnregisteredRecurrenceError`` from every entry point.

The dtype ladders here (``acc_dtype``/``out_dtype``) are shared by the
chip-level shard_map schedules too (``kernels/systolic.py``): Pallas
kernels, the XLA references and the Cannon/halo-exchange lowerings all
widen identically, which is what keeps integer backend parity bit-exact
across every ``lower_plan`` backend.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.partition import (  # noqa: F401  (re-exported ladder)
    DTYPE_BYTES,
    MXU_LANES,
    PACKING,
    PACKING_TPU,
    SUBLANES,
)

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids import cycles
    from repro.core.mapper import ExecutionPlan
    from repro.core.recurrence import UniformRecurrence


# ---------------------------------------------------------------------------
# compiler params, interpret mode, Mosaic-legal tiles, the MXU contraction
# ---------------------------------------------------------------------------

def compiler_params(*, dimension_semantics, **kwargs):
    """``pltpu.CompilerParams`` for one kernel.  ``dimension_semantics``
    is required: reduction grid dims must stay "arbitrary"."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics), **kwargs)


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """True unless a TPU backend is attached (Mosaic compiles TPU-only)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for one kernel call.  None follows the backend.
    ``False`` off a TPU compiles through Mosaic for a described chip
    (the compile-only checks); ``True`` on a TPU is refused, so no
    kernel on the chip ever runs in the interpreter."""
    if interpret is None:
        return default_interpret()
    if interpret and not default_interpret():
        raise ValueError(
            "interpret=True on a TPU backend: kernels on the chip compile "
            "through Mosaic")
    return bool(interpret)


def tile(ext: int, req: int, align: int) -> int:
    """Mosaic-legal block extent for an array dim of size ``ext``.

    A block dim is legal when it is the whole dim or a multiple of the
    hardware tile (``align``: MXU_LANES on the minor dim, the dtype's
    sublane count on the second-minor one).  The request rounds up to
    the next legal extent; the staging layer (ops.py) pads the array to
    a multiple of the result.
    """
    t = -(-max(int(req), 1) // align) * align
    return ext if t >= ext else t


def divisor_tile(ext: int, req: int, align: int) -> int:
    """Mosaic-legal block extent that divides ``ext`` exactly: the
    largest multiple of ``align`` not above ``max(req, align)`` that
    divides ``ext``, else the whole dim (for operands that are not
    padded)."""
    t = max(int(req), align) // align * align
    while t >= align:
        if t < ext and ext % t == 0:
            return t
        t -= align
    return ext


def sublanes(dtype) -> int:
    """Second-minor tile of ``dtype``: 8 rows of 32-bit words, packed
    2x for 16-bit and 4x for 8-bit types."""
    return SUBLANES * max(1, 4 // jnp.dtype(dtype).itemsize)


def _int8_limbs(x) -> tuple:
    """An integer block as int8 limbs of 7 bits, the top one signed:
    ``x == sum_p limb[p] * 2**(7*p)``.  int8 is its own limb; int16
    splits into three (the top in [-2, 1]), int32 into five (the top in
    [-8, 7])."""
    if x.dtype == jnp.int8:
        return (x,)
    n = {2: 3, 4: 5}[x.dtype.itemsize]
    w = x.astype(jnp.int32)
    limbs = [((w >> (7 * p)) & 127).astype(jnp.int8) for p in range(n - 1)]
    return (*limbs, (w >> (7 * (n - 1))).astype(jnp.int8))


def mxu_dot(a, b, acc=None):
    """``a @ b`` for 2-D blocks, accumulated in ``acc_dtype(a.dtype)``,
    in forms the MXU takes.

    Floats go in as they are (float32 at HIGHEST precision, so the MXU
    does not round products to bf16).  int8 goes in as int8 with an int32
    accumulator.  The MXU has no int16 or int32 path: wider integer
    operands split into int8 limbs (``_int8_limbs``) and the limb
    products are summed with their shifts in int32, dropping the ones
    shifted past bit 31.  Every step is ring arithmetic, so the result
    equals the widened int32 dot modulo 2**32 — bit-exact against
    ``ref.py``.  An int16 x int16 block costs nine int8 passes.
    """
    if not jnp.issubdtype(a.dtype, jnp.integer):
        # float32 operands keep float32 products: no single bf16 pass
        precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                     else None)
        return jnp.dot(a, b, precision=precision, preferred_element_type=(
            jnp.float32 if acc is None else acc))
    if a.dtype == b.dtype == jnp.int8:
        return jnp.dot(a, b, preferred_element_type=jnp.int32)
    out = None
    for p, ap in enumerate(_int8_limbs(a)):
        for q, bq in enumerate(_int8_limbs(b)):
            shift = 7 * (p + q)
            if shift >= 32:
                continue
            part = jnp.dot(ap, bq, preferred_element_type=jnp.int32)
            part = part * (1 << shift)
            out = part if out is None else out + part
    return out


def acc_dtype(dtype):
    """Accumulator dtype ladder: integer inputs -> int32, else float32."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jnp.int32
    return jnp.float32


def out_dtype(dtype):
    """Default output dtype: int accumulations widen to int32."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jnp.int32
    return jnp.dtype(dtype)


def packing_factor(dtype_name: str, packing: str = "tpu") -> float:
    """MACs/cycle multiplier of ``dtype_name`` on the chosen packing ladder
    (shared with the mapper's cost model — see core/partition.py)."""
    ladder = PACKING_TPU if packing == "tpu" else PACKING
    return ladder.get(dtype_name, 1.0)


# ---------------------------------------------------------------------------
# plan-derived kernel parameters
# ---------------------------------------------------------------------------

def grid_semantics(rec: "UniformRecurrence", grid_loops) -> tuple[str, ...]:
    """Pallas dimension semantics for a kernel grid derived from the IR.

    ``grid_loops``: one entry per grid dimension — a loop name, or a tuple
    of fused loop names (e.g. conv2d's flattened (p, q) reduction).  A grid
    dimension revisits its output block iff it carries a reduction loop,
    which is exactly Mosaic's "arbitrary"; everything else is "parallel".
    """
    sems = []
    for entry in grid_loops:
        loops = entry if isinstance(entry, tuple) else (entry,)
        red = any(l in rec.reduction_loops for l in loops)
        sems.append("arbitrary" if red else "parallel")
    return tuple(sems)


def plan_kernel_kwargs(plan: "ExecutionPlan") -> dict:
    """Kernel-call kwargs (block shapes + dimension semantics) from a plan.

    The partition's per-loop block extents become the Pallas BlockSpec
    tiles (via the recurrence's registered ``KernelSpec.block_kwargs``);
    the spec's grid loops plus the recurrence's reduction loops become the
    grid's dimension semantics.  Raises ``UnregisteredRecurrenceError``
    for recurrences without a KernelSpec.
    """
    from . import registry

    rec = plan.recurrence
    spec = registry.get(rec.name)
    kw = dict(spec.block_kwargs(plan))
    kw["dimension_semantics"] = grid_semantics(rec, spec.grid_loops)
    return kw


def execute_plan(plan: "ExecutionPlan", *operands,
                 interpret: bool | None = None, out_dtype=None):
    """Execute an ExecutionPlan on concrete operands via its Pallas kernel.

    Dispatch is a ``kernels/registry.py`` lookup: the recurrence's
    ``KernelSpec`` declares the operand arity and the Pallas lowering
    (an ops.py staging wrapper — see each spec for the operand
    convention, e.g. mm takes ``(a[m,k], b[k,n])``, mttkrp takes
    ``(x[i,k,l], b[k,j], c[l,j])``).

    Block shapes, grid and dimension semantics come from the plan; the
    staging-layer data movement (padding to Mosaic-legal tiles, window
    stacking, complex lowering) is ops.py's.  ``interpret`` resolves
    through ``resolve_interpret``.  ``out_dtype`` (kernels that
    support it, e.g. mm/bmm) requests the accumulator flush dtype — the
    MXU-native way to get fp32 results from low-precision operands
    without materializing upcast inputs.
    """
    from . import registry

    rec = plan.recurrence
    spec = registry.get(rec.name)
    if len(operands) != spec.arity:
        raise ValueError(
            f"{rec.name} expects {spec.arity} operands, got {len(operands)}")
    kw = plan_kernel_kwargs(plan)
    sem = kw.pop("dimension_semantics")
    if out_dtype is not None:
        kw["out_dtype"] = out_dtype
    with jax.named_scope(f"widesa.{rec.name}"):
        return spec.pallas(*operands, **kw, dimension_semantics=sem,
                           interpret=resolve_interpret(interpret))
