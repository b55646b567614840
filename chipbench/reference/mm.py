"""Plain reference for a matrix multiplication: ``jnp.dot`` at the
precision the configuration states, and the comparison that decides
``correct``.  Imports nothing of the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@jax.jit
def _dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _bf16_head(x):
    """The bf16-representable head of a float32 array: its top 16 bits,
    cut by a mask (a convert to bfloat16 and back is an identity that
    XLA may fold away)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@jax.jit
def _dot_bf16x3(a, b):
    """The three-pass bf16 product written out, for backends that ignore
    ``Precision.HIGH`` (the CPU computes every float32 dot in full): each
    operand splits into a bf16 head and a bf16 tail, every partial
    product is exact, and the tail x tail term is dropped."""
    ah, bh = _bf16_head(a), _bf16_head(b)
    al, bl = _bf16_head(a - ah), _bf16_head(b - bh)
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.jit
def _dot_high(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGH,
                   preferred_element_type=jnp.float32)


def matmul(a, b, precision: str = "highest"):
    """C = A @ B in float32.  ``precision`` "high" is the control: the
    MXU's three-pass bf16 product (``Precision.HIGH``) instead of the
    full float32 one, written out where the backend has no such pass."""
    if precision == "highest":
        return _dot_highest(a, b)
    if jax.default_backend() == "tpu":
        return _dot_high(a, b)
    return _dot_bf16x3(a, b)


@jax.jit
def _rel_err(out, ref):
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
    return err / jnp.max(jnp.abs(ref))


def rel_err(out, ref) -> float:
    """max |out - ref| / max |ref|: the error of the worst entry against
    the largest answer.  NaN where ``out`` holds a non-finite value."""
    if not bool(jnp.all(jnp.isfinite(out))):
        return float("nan")
    return float(_rel_err(out, ref))
