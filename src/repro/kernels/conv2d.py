"""2-D convolution kernel (paper Table II, [h, w, p, q]).

TPU adaptation (DESIGN.md §2): the paper's DMA-module constructor (§IV)
reorganizes the input stream for the AIE array; here the staging layer
(ops.conv2d) builds the shifted-window stack

    S[p*Q + q, h, w] = I[h + p, w + q]

so the convolution becomes the uniform MM recurrence

    O[h, w] = sum_s  F_flat[s] * S[s, h, w]

— the same systolic mapping the paper derives (conv's reduction loops
p,q are the time loops; h,w are the space loops).  The kernel below
consumes the stack with disjoint (sublane, lane)-aligned blocks (no halo
reads inside the kernel, exactly like AIE cores that only see DMA-fed
local buffers) and sums scalar x plane on the vector unit, with the PQ
filter taps in SMEM: a rank-1 contraction has no MXU shape, and the VPU
path is the same for every dtype (int8/int16 planes widen to int32 lanes
in-register).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime


def conv_kernel(f_ref, s_ref, o_ref, acc_ref):
    """f_ref: (S,) filter taps in SMEM (accumulator dtype); s_ref:
    (bs, bh, bw) window stack block."""
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bs = s_ref.shape[0]
    acc = acc_ref[...]
    for s in range(bs):
        acc = acc + f_ref[l * bs + s] * s_ref[s].astype(acc.dtype)
    acc_ref[...] = acc

    @pl.when(l == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bh", "bw", "bs", "interpret", "out_dtype", "dimension_semantics",
    ),
)
def conv2d_stacked(
    stack: jax.Array,
    filt_flat: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    bs: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """O[h,w] = sum_s stack[s,h,w] * filt_flat[s].

    ``stack``: (S, H, W) shifted windows; ``filt_flat``: (S,).
    """
    s, h, w = stack.shape
    assert filt_flat.shape == (s,)
    if bs is None:
        bs = s
    assert h % bh == 0 and w % bw == 0 and s % bs == 0
    if out_dtype is None:
        out_dtype = runtime.out_dtype(stack.dtype)
    acc_dtype = runtime.acc_dtype(stack.dtype)

    grid = (h // bh, w // bw, s // bs)
    return pl.pallas_call(
        conv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bs, bh, bw), lambda i, j, l: (l, i, j)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), out_dtype),
        scratch_shapes=[pltpu.VMEM((bh, bw), acc_dtype)],
        interpret=runtime.resolve_interpret(interpret),
        compiler_params=runtime.compiler_params(
            dimension_semantics=(
                dimension_semantics or ("parallel", "parallel", "arbitrary")
            ),
        ),
    )(filt_flat.astype(acc_dtype), stack)
