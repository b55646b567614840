"""Jacobi2D 5-point stencil kernel (promoted out of the conv2d workaround).

Until PR 4 the jacobi2d recurrence borrowed ``conv2d.conv2d_stacked`` —
a generic window contraction whose reduction loop rides a third grid
dimension with a VMEM accumulator.  The stencil does not need any of
that: the star has a fixed 5 planes that always fit one block, so the
kernel below contracts them in a single grid visit per output tile
(grid = (i, j), both "parallel"; no scratch, no revisits), as a sum of
scalar x plane on the vector unit with the star weights in SMEM.  The staging
layer (ops.jacobi2d / ops.jacobi2d_ms) still builds the shifted-point
stack

    S[s, i, j] = G[i + di_s, j + dj_s]    (s indexes JACOBI2D_OFFSETS)

— the PL DMA-module analogue, identical to conv/fir — and the multi-sweep
wrapper re-embeds each sweep's interior into the fixed boundary ring,
which is exactly the flow dependence the jacobi2d_ms recurrence declares
on its sweep loop.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def jacobi_kernel(w_ref, s_ref, o_ref):
    """One (bh, bw) output tile: o = sum_s w[s] * stack[s] (all planes
    resident — single visit, no accumulator scratch).  ``w_ref`` holds
    the weights in SMEM in the accumulator dtype."""
    acc = w_ref[0] * s_ref[0].astype(w_ref.dtype)
    for s in range(1, s_ref.shape[0]):
        acc = acc + w_ref[s] * s_ref[s].astype(w_ref.dtype)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bh", "bw", "interpret", "out_dtype",
                     "dimension_semantics"),
)
def jacobi2d_stacked(
    stack: jax.Array,
    weights: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """O[i,j] = sum_s stack[s,i,j] * weights[s].

    ``stack``: (S, H, W) shifted star points; ``weights``: (S,).
    """
    s, h, w = stack.shape
    assert weights.shape == (s,)
    assert h % bh == 0 and w % bw == 0, ((h, w), (bh, bw))
    if out_dtype is None:
        out_dtype = runtime.out_dtype(stack.dtype)

    grid = (h // bh, w // bw)
    return pl.pallas_call(
        jacobi_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((s, bh, bw), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), out_dtype),
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=(
                dimension_semantics or ("parallel", "parallel")
            ),
        ),
    )(weights.astype(runtime.acc_dtype(stack.dtype)), stack)
