"""Pallas TPU fused RMSNorm.

Row-blocked: each program normalizes a (br, d) tile fully in VMEM (one HBM
read + one write; XLA otherwise materializes the fp32 upcast). d is the
model dim (always 128-aligned for the assigned architectures).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_rows(x, scale, eps: float = 1e-5, *, br: int = 256,
                 interpret: bool = False):
    """x: (R, d); scale: (d,). Returns (R, d) of x.dtype."""
    R, d = x.shape
    br = min(br, R)
    assert R % br == 0, (R, br)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale)
