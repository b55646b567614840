"""MTTKRP kernel (HPC tensor-decomposition hot loop, beyond-paper).

M[i,j] += X[i,k,l] * B[k,j] * C[l,j] — the matricized-tensor times
Khatri-Rao product that dominates CP tensor decomposition.  Two reduction
loops (k, l) stream through two "arbitrary" grid dimensions while the
(i, j) output tile stays resident in the VMEM accumulator — the same
latency-hiding structure as the WideSA MM, with a rank-3 operand.

Per (k, l) grid step the block contraction is

    acc[i,j] += sum_{k0,l0} X[i,k0,l0] * B[k0,j] * C[l0,j]
              = sum_{k0} (X[k0][i,:] @ C)[i,j] * B[k0,j]

one 2-D MXU dot per k0 of the block, scaled by the row B[k0] on the
vector unit.  The staging layer (ops.mttkrp) hands X over k-major, as
(K, I, L), so each k0 slice is an (i, l) tile whose minor dims follow
the (sublane, lane) tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def mttkrp_kernel(x_ref, b_ref, c_ref, o_ref, acc_ref):
    """x: (bk, bi, bl); b: (bk, bj) in the accumulator dtype (its rows
    are read one at a time); c: (bl, bj) -> o: (bi, bj)."""
    first = jnp.logical_and(pl.program_id(2) == 0, pl.program_id(3) == 0)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_t = acc_ref.dtype
    c = c_ref[...]

    def body(k0, acc):
        xc = runtime.mxu_dot(x_ref[k0], c, acc_t)
        return acc + xc * b_ref[pl.ds(k0, 1), :]

    acc_ref[...] = jax.lax.fori_loop(0, x_ref.shape[0], body, acc_ref[...])

    last = jnp.logical_and(
        pl.program_id(2) == pl.num_programs(2) - 1,
        pl.program_id(3) == pl.num_programs(3) - 1,
    )

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bi", "bj", "bk", "bl", "interpret", "out_dtype",
        "dimension_semantics",
    ),
)
def mttkrp(
    xt: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 16,
    bl: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """M[i,j] = sum_{k,l} X[i,k,l] * B[k,j] * C[l,j], with X given
    k-major: ``xt[k, i, l] = X[i, k, l]``."""
    nk, ni, nl = xt.shape
    nk2, nj = b.shape
    nl2, nj2 = c.shape
    assert (nk, nl, nj) == (nk2, nl2, nj2), (xt.shape, b.shape, c.shape)
    assert ni % bi == 0 and nj % bj == 0 and nk % bk == 0 and nl % bl == 0, (
        (ni, nj, nk, nl), (bi, bj, bk, bl))
    if out_dtype is None:
        out_dtype = runtime.out_dtype(xt.dtype)
    acc_dtype = runtime.acc_dtype(xt.dtype)

    grid = (ni // bi, nj // bj, nk // bk, nl // bl)
    return pl.pallas_call(
        mttkrp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bi, bl), lambda i, j, k, l: (k, i, l)),
            pl.BlockSpec((bk, bj), lambda i, j, k, l: (k, j)),
            pl.BlockSpec((bl, bj), lambda i, j, k, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, k, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ni, nj), out_dtype),
        scratch_shapes=[pltpu.VMEM((bi, bj), acc_dtype)],
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=(
                dimension_semantics
                or ("parallel", "parallel", "arbitrary", "arbitrary")
            ),
        ),
    )(xt, b.astype(acc_dtype), c)
