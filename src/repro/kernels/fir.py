"""FIR filter kernel (paper Table II, [n, taps]).

Same staging-layer strategy as conv2d (the paper's DMA-module analogue):
ops.fir builds the shifted stack S[t, n] = x[n + t], after which FIR is the
uniform MM recurrence  y[n] = sum_t h[t] * S[t, n]  — per (T, bn) block a
sum of scalar x row on the vector unit, with the taps in SMEM and the
output a (1, bn) row block.  n is the space loop (mapped across
blocks/PEs), t the time loop, exactly the paper's FIR mapping.

Complex FIR (cfloat) is lowered by the ops wrapper to four real FIR passes
(re*re - im*im, re*im + im*re) — the MXU-native equivalent of the AIE's
native cfloat MAC (DESIGN.md §9.3).
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def fir_kernel(h_ref, s_ref, o_ref):
    """h_ref: (T,) taps in SMEM (accumulator dtype); s_ref: (T, bn)
    shifted stack -> o_ref: (1, bn)."""
    acc = h_ref[0] * s_ref[0:1, :].astype(h_ref.dtype)
    for t in range(1, s_ref.shape[0]):
        acc = acc + h_ref[t] * s_ref[t:t + 1, :].astype(h_ref.dtype)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bn", "interpret", "out_dtype", "dimension_semantics"),
)
def fir_stacked(
    stack: jax.Array,
    taps: jax.Array,
    *,
    bn: int = 1024,
    interpret: bool | None = None,
    out_dtype=None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """y[n] = sum_t taps[t] * stack[t, n]."""
    t, n = stack.shape
    assert taps.shape == (t,)
    assert n % bn == 0, (n, bn)
    if out_dtype is None:
        out_dtype = runtime.out_dtype(stack.dtype)
    out = pl.pallas_call(
        fir_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((t, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), out_dtype),
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=dimension_semantics or ("parallel",),
        ),
    )(taps.astype(runtime.acc_dtype(stack.dtype)), stack)
    return out[0]
