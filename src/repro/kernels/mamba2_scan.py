"""Pallas TPU chunked SSD scan (Mamba2).

One program per (batch, head, chunk); the chunk axis is sequential and the
(P, N) per-head state lives in VMEM scratch across chunk steps:

  grid = (B, H, S // chunk)                       — chunk axis "arbitrary"
  xh block (chunk, P), dt/a blocks (chunk, 128), B/C blocks (chunk, N)
  scratch  h (P, N) f32

Per chunk the intra-block term is two MXU matmuls ((Q,N)x(N,Q) and
(Q,Q)x(Q,P)) plus the decay mask; the inter-block term applies the carried
state. This mirrors ``repro.models.layers.mamba2.ssd_chunked`` (the oracle)
with the state kept resident in VMEM instead of a lax.scan carry.

dt/a are fed pre-broadcast to (S, 128) lanes so the kernel reads column 0 —
scalar-per-row values are lane-padded for TPU-friendly layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[:, 0].astype(jnp.float32)         # (Q,)
    a = a_ref[:, 0].astype(jnp.float32)           # (Q,) = dt * A  (negative)
    Bm = b_ref[...].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)           # (Q, N)

    cum = jnp.cumsum(a)                           # (Q,)
    seg = cum[:, None] - cum[None, :]             # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    M = CB * L * dt[None, :]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)
    # inter-chunk: y += exp(cum) * C @ h^T
    h = h_ref[...]                                # (P, N)
    y_off = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(cum)[:, None]
    y_ref[...] = y.astype(y_ref.dtype)
    # state update: h' = exp(sum a) h + sum_j w_j x_j B_j^T
    w = jnp.exp(cum[-1] - cum) * dt               # (Q,)
    st = jax.lax.dot_general(x * w[:, None], Bm, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = h * jnp.exp(cum[-1]) + st


def ssd_scan_bshpn(xh, dt, a, Bm, Cm, *, chunk: int = 128,
                   interpret: bool = False):
    """xh: (B,S,H,P); dt,a: (B,S,H); Bm,Cm: (B,S,N) -> y: (B,S,H,P).

    ``a = dt * A`` (log-decay per step). S % chunk == 0.
    """
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    nc = S // chunk
    # lane-pad per-row scalars to (B,S,H,128) for TPU layout
    dt_l = jnp.broadcast_to(dt[..., None], (B, S, H, 128))
    a_l = jnp.broadcast_to(a[..., None], (B, S, H, 128))
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, None, P),
                         lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((None, chunk, None, 128),
                         lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((None, chunk, None, 128),
                         lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, None, P),
                               lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dt_l, a_l, Bm, Cm)
