"""Held experts' decode FFN: a pair of Pallas kernels that read one
layer's expert weights in place from the stacked ``[L, n, K, N]``
parameters.

A decode step's tokens are few (one a lane), so every held expert is
taken against all of them:

  * ``_gate_up_kernel``: ``h[e] = act(x @ wg[l, e]) * (x @ wu[l, e])``,
    grid ``(expert, K tile)``, gate and up accumulated in float32;
  * ``_down_kernel``: ``y = sum_e w[:, e] * (h[e] @ wd[l, e])``, grid
    ``(N tile, expert)``, the sum over experts in float32.

``w[T, n]`` holds each token's top-k weight of each held expert: 0 where
the token did not pick the expert, or is not a real row, and there the
kernel adds an exact zero.  Every chosen (token, held expert) pair gets
the contribution the grouped matmul gives it; the MXU works on the
others too, which costs no time while the weights' DMA is the longer
part (up to about ``MAX_ROWS`` rows a bf16 expert on a TPU v5e).

The layer arrives by scalar prefetch and the weight BlockSpecs index
``(layer, expert, k, n)``, so each weight tile is read once, straight
from the stacks, double-buffered by the pipeline.  No per-layer slice of
the stacks is made: a caller scanning over layers keeps the stacks out
of the scanned inputs and hands them in whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime

#: Target bytes of one weight tile in VMEM (the pipeline keeps two of
#: each weight operand; tiles of 1 to 8 MiB read alike on a TPU v5e).
BLOCK_BYTES = 2 * 1024 * 1024
#: Most tokens the kernels take.  Their MXU work grows with the tokens
#: and their bytes do not: at 197 TFLOP/s and 819 GB/s a bf16 weight
#: tile's DMA covers the matmul of about 240 rows, so 128 keeps the
#: kernels bound by the weights' bytes with room for a half-busy MXU.
MAX_ROWS = 128
#: Weight dtypes the kernels read.
DTYPES = tuple(jnp.dtype(d) for d in (jnp.bfloat16, jnp.float32))


def engages(wg, wu, wd, rows: int) -> bool:
    """Whether the kernels serve ``rows`` tokens against the stacks wg,
    wu ``[L, n, d, ff]`` and wd ``[L, n, ff, d]``: a dtype they read,
    widths of whole 128-lane tiles and at most ``MAX_ROWS`` tokens."""
    if wg.ndim != 4 or wg.shape != wu.shape:
        return False
    layers, n, d, ff = wg.shape
    return (wd.shape == (layers, n, ff, d) and jnp.dtype(wg.dtype) in DTYPES
            and wg.dtype == wu.dtype == wd.dtype
            and d % runtime.MXU_LANES == 0 and ff % runtime.MXU_LANES == 0
            and 0 < rows <= MAX_ROWS)


def _tile(ext: int, width: int, itemsize: int) -> int:
    """Largest multiple of 128 that divides ``ext`` and keeps a
    ``[tile, width]`` block within ``BLOCK_BYTES`` (at least 128)."""
    cap = max(BLOCK_BYTES // (width * itemsize), runtime.MXU_LANES)
    t = min(ext, cap) // runtime.MXU_LANES * runtime.MXU_LANES
    while ext % t:
        t -= runtime.MXU_LANES
    return t


def _dot(a, b):
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _gate_up_kernel(layer_ref, x_ref, wg_ref, wu_ref, h_ref, g_acc, u_acc,
                    *, tk, act):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[:, pl.ds(pl.multiple_of(k * tk, tk), tk)]
    g_acc[...] += _dot(x, wg_ref[...])
    u_acc[...] += _dot(x, wu_ref[...])

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        h_ref[...] = (act(g_acc[...]) * u_acc[...]).astype(h_ref.dtype)


def _down_kernel(layer_ref, w_ref, h_ref, wd_ref, y_ref, acc):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    w = w_ref[e]                                  # [T, 1]
    part = _dot(h_ref[e], wd_ref[...])            # [T, tn]
    acc[...] += jnp.where(w != 0, w * part, 0.0)

    @pl.when(e == pl.num_programs(1) - 1)
    def _():
        y_ref[...] = acc[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def held_experts_ffn(x, wg, wu, wd, layer, w, *, act="silu",
                     interpret=None):
    """The held experts' weighted FFN of one layer for every token.

    x [T, d]; wg, wu [L, n, d, ff] and wd [L, n, ff, d], the stacks the
    kernels read layer ``layer`` of (int32 scalar); w [T, n] float32,
    each token's weight of each held expert (0: not chosen).  Returns
    y [T, d] in x's dtype: ``sum_e w[:, e] * FFN_e(x)``, in float32
    until the cast.
    """
    t, d = x.shape
    n, ff = wg.shape[1], wg.shape[3]
    # the gate/up tiles are [tk, ff] of d rows, the down tiles [ff, tn]
    # of d columns: one width serves both
    tk = tn = _tile(d, ff, jnp.dtype(wg.dtype).itemsize)
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    dt = x.dtype
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    interpret = runtime.resolve_interpret(interpret)

    h = pl.pallas_call(
        functools.partial(_gate_up_kernel, tk=tk, act=fn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, d // tk),
            in_specs=[
                pl.BlockSpec((t, d), lambda e, k, l: (0, 0)),
                pl.BlockSpec((None, None, tk, ff),
                             lambda e, k, l: (l[0], e, k, 0)),
                pl.BlockSpec((None, None, tk, ff),
                             lambda e, k, l: (l[0], e, k, 0)),
            ],
            out_specs=pl.BlockSpec((None, t, ff), lambda e, k, l: (e, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t, ff), jnp.float32),
                            pltpu.VMEM((t, ff), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, t, ff), wg.dtype),
        name="held_experts_gate_up",
        interpret=interpret,
        compiler_params=runtime.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(layer, x.astype(wg.dtype), wg, wu)

    # the expert axis leads, so that each expert's weights are one [T, 1]
    # row of the block
    w = jnp.transpose(w.astype(jnp.float32))[..., None]
    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // tn, n),
            in_specs=[
                pl.BlockSpec((n, t, 1), lambda j, e, l: (0, 0, 0)),
                pl.BlockSpec((n, t, ff), lambda j, e, l: (0, 0, 0)),
                pl.BlockSpec((None, None, ff, tn),
                             lambda j, e, l: (l[0], e, 0, j)),
            ],
            out_specs=pl.BlockSpec((t, tn), lambda j, e, l: (0, j)),
            scratch_shapes=[pltpu.VMEM((t, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((t, d), dt),
        name="held_experts_down",
        interpret=interpret,
        compiler_params=runtime.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(layer, w, h, wd)
