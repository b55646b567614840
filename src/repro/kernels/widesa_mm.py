"""WideSA systolic matmul — the flagship Pallas TPU kernel (paper's MM).

The ExecutionPlan's kernel-scope tiles (N0, M0, K0) become the BlockSpec
shapes; the latency-hiding accumulator (N2, M2) is the fp32/int32 VMEM
scratch that stays resident across the K grid dimension (the systolic time
loop), so the MXU pipeline never stalls on the accumulation carry — the
direct analogue of the paper's §III-B3.

Grid layout: (i, j, k) with k innermost ("arbitrary" — it revisits the same
output block).  Mosaic double-buffers the A/B input blocks automatically
(multiple-buffering == the paper's DMA ping-pong).

Supported dtypes (paper Table II): float32, bfloat16 (accum f32), int8
(native MXU, accum int32), int16 (three int8 limbs per operand — see
``runtime.mxu_dot``; accum int32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One (N0, M0) output tile; K streams through the k grid dim."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += runtime.mxu_dot(a_ref[...], b_ref[...], acc_ref.dtype)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm", "bn", "bk", "interpret", "out_dtype", "dimension_semantics",
    ),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """C[m,n] = A[m,k] @ B[k,n] with WideSA plan tiles.

    Shapes must be divisible by the tiles, and the tiles Mosaic-legal
    (ops.matmul legalizes and pads).  Tile sizes
    and ``dimension_semantics`` normally come from an ExecutionPlan via
    ``runtime.execute_plan``; the defaults reproduce the plan the mapper
    picks for MXU-aligned MM.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k), (bm, bn, bk))
    if out_dtype is None:
        out_dtype = runtime.out_dtype(a.dtype)
    acc_dtype = runtime.acc_dtype(a.dtype)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=(
                dimension_semantics or ("parallel", "parallel", "arbitrary")
            ),
        ),
    )(a, b)
