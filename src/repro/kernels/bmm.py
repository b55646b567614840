"""Batched matmul kernel (the model-stack shape, beyond-paper workload).

C[b,i,j] += A[b,i,k] * B[b,k,j] — attention heads, expert stacks and
microbatched layers all reduce to this recurrence.  The batch loop maps to
a "parallel" grid dimension with block extent 1 (each program instance owns
one batch slice), and the (i, j, k) tiling is exactly the WideSA MM
mapping: the plan's kernel-scope tiles become the BlockSpec shapes and the
latency-hiding accumulator stays resident in VMEM across the k grid
dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def bmm_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One (1, N0, M0) output tile of one batch; K streams through grid."""

    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += runtime.mxu_dot(a_ref[0], b_ref[0], acc_ref.dtype)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm", "bn", "bk", "interpret", "out_dtype", "dimension_semantics",
    ),
)
def bmm(
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
    """C[b,m,n] = A[b,m,k] @ B[b,k,n] with WideSA plan tiles per batch."""
    nb, m, k = a.shape
    nb2, k2, n = b.shape
    assert (nb, k) == (nb2, k2), (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k), (bm, bn, bk))
    if out_dtype is None:
        out_dtype = runtime.out_dtype(a.dtype)
    acc_dtype = runtime.acc_dtype(a.dtype)

    grid = (nb, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        bmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bt, i, j, l: (bt, i, l)),
            pl.BlockSpec((1, bk, bn), lambda bt, i, j, l: (bt, l, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bt, i, j, l: (bt, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=(
                dimension_semantics
                or ("parallel", "parallel", "parallel", "arbitrary")
            ),
        ),
    )(a, b)
