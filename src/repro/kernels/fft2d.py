"""2-D FFT as MXU matmul stages (paper Table II, [row, col]).

Hardware adaptation (DESIGN.md §9.3): AIE cores have native cfloat MACs, so
the paper's 2-D FFT streams complex butterflies through the array.  The MXU
has no complex datapath — the TPU-idiomatic equivalent is the matrix form
of the DFT:   X2 = F_R @ X @ F_C   (two fft2d_stage uniform recurrences),
with complex arithmetic lowered to real-plane matmuls on the WideSA MM
kernel.  Each stage therefore inherits the MM systolic mapping and tiles.

Complex product uses the 3-multiplication (Karatsuba/Gauss) form by
default:  k1 = Br(Ar+Ai), k2 = Ar(Bi-Br), k3 = Ai(Br+Bi)
          Re = k1 - k3, Im = k1 + k2      — 25 % fewer MXU passes than the
naive 4-mult form (a beyond-paper optimization; toggle with three_mult).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import runtime
from .widesa_mm import matmul as mm


def dft_matrix(n: int) -> tuple[jax.Array, jax.Array]:
    """Real/imag float32 planes of the n-point DFT matrix.

    Built with jnp, so under jit the planes are computed on the device
    instead of entering the program as an n x n constant.  The phase
    index ``k*j mod n`` is exact in int32, which keeps the float32
    angles accurate to an ulp of 2*pi.
    """
    if n * n >= 2**31:
        raise ValueError(f"{n}-point DFT phase index overflows int32")
    k = jnp.arange(n, dtype=jnp.int32)
    ang = ((k[:, None] * k[None, :]) % n).astype(jnp.float32) * (
        -2.0 * np.pi / n)
    return jnp.cos(ang), jnp.sin(ang)


def _cmul_mm(ar, ai, br, bi, *, three_mult: bool, bm, bn, bk, interpret,
             dimension_semantics=None):
    """Complex matmul (A @ B) via real MM kernel calls."""
    dot = functools.partial(
        mm, bm=bm, bn=bn, bk=bk, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
    if three_mult:
        k1 = dot(ar + ai, br)
        k2 = dot(ar, bi - br)
        k3 = dot(ai, br + bi)
        return k1 - k3, k1 + k2
    rr = dot(ar, br)
    ii = dot(ai, bi)
    ri = dot(ar, bi)
    ir = dot(ai, br)
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
) -> tuple[jax.Array, jax.Array]:
    """2-D DFT of a (R, C) complex grid held as two real planes."""
    r, c = x_re.shape
    # stage 1 is (r,r)@(r,c), stage 2 (r,c)@(c,c): the tiles divide the
    # extents exactly (the DFT planes are not padded), each stage with its
    # own contraction tile
    bm = runtime.divisor_tile(r, bm, runtime.sublanes(x_re.dtype))
    bn = runtime.divisor_tile(c, bn, runtime.MXU_LANES)
    bk1 = runtime.divisor_tile(r, bk, runtime.MXU_LANES)
    bk2 = runtime.divisor_tile(c, bk, runtime.MXU_LANES)
    fr_re, fr_im = dft_matrix(r)
    fc_re, fc_im = dft_matrix(c)

    # stage 1: rows — Y = F_R @ X
    y_re, y_im = _cmul_mm(
        fr_re, fr_im, x_re, x_im,
        three_mult=three_mult, bm=bm, bn=bn, bk=bk1, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
    # stage 2: cols — Z = Y @ F_C
    z_re, z_im = _cmul_mm(
        y_re, y_im, fc_re, fc_im,
        three_mult=three_mult, bm=bm, bn=bn, bk=bk2, interpret=interpret,
        dimension_semantics=dimension_semantics,
    )
    return z_re, z_im
