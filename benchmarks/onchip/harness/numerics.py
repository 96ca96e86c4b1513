"""Arithmetic shared by the plain reference and its control.

``mode`` picks the precision of every weight matmul:
  "f32"  float32 at ``Precision.HIGHEST`` (the reference);
  "fp8"  both operands rounded to float8_e4m3fn with one scale per
         tensor, accumulated in float32 (the control: the step below the
         bf16 that the configurations state).
Norms, gates, softmax and the recurrent state stay float32 in both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0              # largest finite float8_e4m3fn


def draw(key, shape, scale, dtype):
    """A weight as the program draws it: a float32 normal, scaled, in the
    dtype it is served in; returned as float32."""
    return (jax.random.normal(key, shape) * scale).astype(dtype).astype(F32)


def quant_fp8(x):
    s = jnp.max(jnp.abs(x)) / _FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(spec: str, x, w, mode: str):
    if mode == "fp8":
        x, w = quant_fp8(x), quant_fp8(w)
    elif mode != "f32":
        raise ValueError(f"unknown reference mode {mode!r}")
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def ein(spec: str, *xs):
    """A float32 product that is not a weight matmul (state, attention)."""
    return jnp.einsum(spec, *xs, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
