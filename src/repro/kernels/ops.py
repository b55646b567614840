"""Public jit'd wrappers for the WideSA kernels.

Each wrapper owns the staging-layer data movement (the paper's PL DMA
module, §IV): Mosaic-legal tiles (``runtime.tile``: a requested block
extent rounds up to the whole dim or a multiple of the (sublane, lane)
tile) with padding to tile multiples, shifted-window stacking for
conv/fir, and complex lowering for FFT/complex FIR.  Model code calls these
(`use_pallas=True` paths); the dry-run uses the XLA path since Mosaic only
lowers on TPU targets — ``interpret=None`` resolves through
``runtime.resolve_interpret`` (interpret mode everywhere but real TPU).

Plan-driven callers should go through ``runtime.execute_plan`` instead,
which derives the tile/semantics kwargs below from a mapper ExecutionPlan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.recurrence import JACOBI2D_9PT_OFFSETS, JACOBI2D_OFFSETS

from . import bmm as _bmm
from . import conv2d as _conv
from . import fir as _fir
from . import fft2d as _fft
from . import jacobi2d as _jacobi
from . import mttkrp as _mttkrp
from . import runtime
from . import widesa_mm as _mm
from .runtime import MXU_LANES


def _lane(ext: int, req: int) -> int:
    """Legal block extent of a minor (lane) dim."""
    return runtime.tile(ext, req, MXU_LANES)


def _sub(ext: int, req: int, dtype) -> int:
    """Legal block extent of a second-minor (sublane) dim."""
    return runtime.tile(ext, req, runtime.sublanes(dtype))


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = []
    for dim, m in zip(x.shape, mults):
        pads.append((0, (-dim) % m))
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """C = A @ B with automatic padding to the plan tiles."""
    m, k = a.shape
    _, n = b.shape
    bm_, bn_, bk_ = _sub(m, bm, a.dtype), _lane(n, bn), _lane(k, bk)
    ap = _pad_to(a, (bm_, bk_))
    bp = _pad_to(b, (bk_, bn_))
    out = _mm.matmul(ap, bp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
                     dimension_semantics=dimension_semantics)
    return out[:m, :n]


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
    """C[b] = A[b] @ B[b] per batch, with automatic padding to the tiles."""
    nb, m, k = a.shape
    _, _, n = b.shape
    bm_, bn_, bk_ = _sub(m, bm, a.dtype), _lane(n, bn), _lane(k, bk)
    ap = _pad_to(a, (1, bm_, bk_))
    bp = _pad_to(b, (1, bk_, bn_))
    out = _bmm.bmm(ap, bp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
                   out_dtype=out_dtype,
                   dimension_semantics=dimension_semantics)
    return out[:, :m, :n]


def _star2d(
    grid: jax.Array,
    weights: jax.Array,
    offsets: tuple[tuple[int, int], ...],
    *,
    bh: int,
    bw: int,
    interpret: bool | None,
    dimension_semantics: tuple[str, ...] | None,
) -> jax.Array:
    """Shared star staging: one weighted sweep over the grid interior.

    The star is staged as a shifted-point stack (the DMA-module analogue,
    same as conv/fir) and contracted on the dedicated stencil kernel
    (``kernels/jacobi2d.py`` — plane-count generic).  ``offsets`` are
    padded-grid (di, dj) per star point; the pad width is derived from
    them (1 for the 5-point star, 2 for the radius-2 9-point star).
    """
    from . import ref

    pad = ref._star_pad(offsets)
    h, w = grid.shape
    oh, ow = h - 2 * pad, w - 2 * pad
    if oh <= 0 or ow <= 0:
        raise ValueError(
            f"star stencil needs a grid of at least "
            f"{2 * pad + 1}x{2 * pad + 1} (got {grid.shape}): "
            "no interior to update")
    stack = jnp.stack(
        [grid[di : di + oh, dj : dj + ow] for di, dj in offsets]
    )  # (S, oh, ow)
    bh_, bw_ = _sub(oh, bh, grid.dtype), _lane(ow, bw)
    stack = _pad_to(stack, (1, bh_, bw_))
    out = _jacobi.jacobi2d_stacked(
        stack, weights, bh=bh_, bw=bw_, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
    return out[:oh, :ow]


def jacobi2d(
    grid: jax.Array,
    weights: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """One weighted 5-point Jacobi sweep over the grid interior.

    ``grid``: (H, W) field; ``weights``: (5,) star weights ordered as
    ``recurrence.JACOBI2D_OFFSETS`` (centre, north, south, west, east).
    Returns the (H-2, W-2) interior update.
    """
    return _star2d(grid, weights, JACOBI2D_OFFSETS, bh=bh, bw=bw,
                   interpret=interpret,
                   dimension_semantics=dimension_semantics)


def jacobi2d_9pt(
    grid: jax.Array,
    weights: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """One weighted 9-point *radius-2* star sweep over the grid interior.

    ``grid``: (H, W) field; ``weights``: (9,) star weights ordered as
    ``recurrence.JACOBI2D_9PT_OFFSETS`` (centre, N1, N2, S1, S2, W1, W2,
    E1, E2).  Returns the (H-4, W-4) interior update — the width-2 halo
    workload at chip level (``kernels/systolic.py``).
    """
    return _star2d(grid, weights, JACOBI2D_9PT_OFFSETS, bh=bh, bw=bw,
                   interpret=interpret,
                   dimension_semantics=dimension_semantics)


def jacobi2d_ms(
    grid: jax.Array,
    weights: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """Multi-sweep Jacobi: ``weights.shape[0]`` weighted 5-point sweeps.

    ``weights``: (T, 5) per-sweep star weights — the sweep count rides in
    the operand, so the (grid, weights) contract matches single-sweep
    ``jacobi2d``.  Each sweep's interior is re-embedded into the fixed
    boundary ring (Dirichlet boundary) before the next sweep consumes it:
    the jacobi2d_ms recurrence's *flow* dependence on the sweep loop,
    executed here as a host-level loop around the stencil kernel.  State
    is promoted to the accumulator dtype (int -> int32) once up front so
    repeated sweeps never narrow intermediate values; all backends (xla
    reference, chip-level halo exchange) share this ladder.
    """
    from . import runtime

    sweeps = weights.shape[0]
    g = grid.astype(runtime.acc_dtype(grid.dtype))
    for t in range(sweeps):
        interior = jacobi2d(
            g, weights[t].astype(g.dtype), bh=bh, bw=bw,
            interpret=interpret, dimension_semantics=dimension_semantics,
        )
        g = g.at[1:-1, 1:-1].set(interior)
    return g[1:-1, 1:-1]


def mttkrp(
    x: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    bi: int = 128,
    bj: int = 128,
    bk: int = 16,
    bl: int = 16,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """M[i,j] = sum_{k,l} X[i,k,l] B[k,j] C[l,j], padded to the tiles.

    Zero padding along k/l adds zero contributions, so the sliced result
    is exact.
    """
    ni, nk, nl = x.shape
    _, nj = b.shape
    bi_, bj_ = _sub(ni, bi, x.dtype), _lane(nj, bj)
    bk_, bl_ = _sub(nk, bk, x.dtype), _lane(nl, bl)
    xp = _pad_to(x.transpose(1, 0, 2), (bk_, bi_, bl_))  # k-major
    bp = _pad_to(b, (bk_, bj_))
    cp = _pad_to(c, (bl_, bj_))
    out = _mttkrp.mttkrp(xp, bp, cp, bi=bi_, bj=bj_, bk=bk_, bl=bl_,
                         interpret=interpret,
                         dimension_semantics=dimension_semantics)
    return out[:ni, :nj]


def conv2d(
    img: jax.Array,
    filt: jax.Array,
    *,
    bh: int = 128,
    bw: int = 128,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """VALID 2-D correlation via the shifted-window stack (DMA staging)."""
    p, q = filt.shape
    h, w = img.shape
    oh, ow = h - p + 1, w - q + 1
    stack = jnp.stack(
        [img[i : i + oh, j : j + ow] for i in range(p) for j in range(q)]
    )  # (p*q, oh, ow)
    bh_, bw_ = _sub(oh, bh, img.dtype), _lane(ow, bw)
    stack = _pad_to(stack, (1, bh_, bw_))
    out = _conv.conv2d_stacked(
        stack, filt.reshape(-1), bh=bh_, bw=bw_, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
    return out[:oh, :ow]


def fir(
    x: jax.Array,
    taps: jax.Array,
    *,
    bn: int = 1024,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
) -> jax.Array:
    """VALID FIR via the shifted stack."""
    t = taps.shape[0]
    n_out = x.shape[0] - t + 1
    stack = jnp.stack([x[i : i + n_out] for i in range(t)])  # (t, n_out)
    bn_ = _lane(n_out, bn)
    stack = _pad_to(stack, (1, bn_))
    out = _fir.fir_stacked(stack, taps, bn=bn_, interpret=interpret,
                           dimension_semantics=dimension_semantics)
    return out[:n_out]


def fir_complex(
    x_re, x_im, h_re, h_im, *, bn: int = 1024, interpret: bool | None = None
):
    """cfloat FIR as four real passes (MXU-native complex lowering)."""
    f = functools.partial(fir, bn=bn, interpret=interpret)
    rr = f(x_re, h_re)
    ii = f(x_im, h_im)
    ri = f(x_re, h_im)
    ir = f(x_im, h_re)
    return rr - ii, ri + ir


def fft2d(
    x_re: jax.Array,
    x_im: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    three_mult: bool = True,
    interpret: bool | None = None,
    dimension_semantics: tuple[str, ...] | None = None,
):
    return _fft.fft2d(
        x_re, x_im,
        bm=bm, bn=bn, bk=bk,
        three_mult=three_mult, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
