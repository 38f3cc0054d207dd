"""Pallas TPU fused InfoNCE loss (paper Eq. 2 hot-spot).

The SSL loss builds a (B, B) logits matrix q @ k^T / tau and immediately
reduces it to a per-row cross-entropy against the diagonal. Fusing the
matmul with the reduction means the logits tile never leaves VMEM:

  grid = (B // br, B // bc)                       — column axis sequential
  q block (br, d), k block (bc, d)
  scratch m/l/g (br, 128) f32  (running max / sum / gold logit)
  out per-row loss (br,)

Inputs are assumed L2-normalized (the wrapper normalizes). Validated in
interpret mode against ``repro.kernels.ref.info_nce_rows_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _infonce_kernel(q_ref, k_ref, o_ref, m_ref, l_ref, g_ref, *,
                    br: int, bc: int, nc: int, inv_tau: float):
    ri = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * inv_tau
    rows = ri * br + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    cols = ci * bc + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    diag = rows == cols
    g_ref[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(diag, logits, 0.0), axis=1, keepdims=True),
        g_ref.shape)
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * corr + jnp.sum(jnp.exp(logits - m_new[:, None]),
                                         axis=-1)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ci == nc - 1)
    def _finalize():
        # loss_i = log(sum_j exp(logit_ij)) - logit_ii
        o_ref[...] = (jnp.log(jnp.maximum(l_ref[:, 0], 1e-30)) + m_ref[:, 0]
                      - g_ref[:, 0]).astype(o_ref.dtype)


def info_nce_rows(q, k, tau: float, *, br: int = 128, bc: int = 128,
                  interpret: bool = False):
    """q, k: (B, d) L2-normalized. Returns per-row losses (B,)."""
    B, d = q.shape
    nr, nc = B // br, B // bc
    kernel = functools.partial(_infonce_kernel, br=br, bc=bc, nc=nc,
                               inv_tau=1.0 / tau)
    return pl.pallas_call(
        kernel,
        grid=(nr, nc),
        in_specs=[
            pl.BlockSpec((br, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k)
