"""Pallas TPU flash attention (GQA, causal / sliding-window).

Online-softmax attention with explicit VMEM tiling:

  grid = (batch, q_heads, S // bq, T // bk)   — last axis sequential
  Q block   (bq, hd)   VMEM
  K/V block (bk, hd)   VMEM, indexed by kv_head = q_head // group
  scratch   acc (bq, hd) f32, m/l (bq, 128) f32 — persists across the kv axis

The kv axis is ``arbitrary`` (sequential) so the scratch carries the
running row-max / row-sum / accumulator; fully-masked KV blocks are skipped
with ``pl.when`` (the roofline win over XLA's dense masking for causal and
sliding-window attention). Block shapes are MXU-aligned: bq, bk multiples
of 128 (the ops wrapper pads head_dim and sequence as needed).

Validated in interpret mode against ``repro.kernels.ref.sdpa_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, window: int, nk: int,
                  scale: float, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: causal => kv block after the last query; window =>
    # kv block entirely before the window of the first query
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[...].astype(jnp.float32)                 # (bk, hd)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len          # exclude zero-padded kv tail
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[:, 0]                                # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = 128, bk: int = 128, scale: float = None,
                         kv_len: int = None, interpret: bool = False):
    """q: (B, Hq, S, hd); k, v: (B, Hkv, T, hd). Returns (B, Hq, S, hd).

    S % bq == 0, T % bk == 0, Hq % Hkv == 0 (the ops wrapper pads).
    ``scale`` must be 1/sqrt(true head dim) when hd is zero-padded.
    """
    B, Hq, S, hd = q.shape
    _, Hkv, T, _ = k.shape
    group = Hq // Hkv
    nq, nk = S // bq, T // bk
    scale = (1.0 / (hd ** 0.5)) if scale is None else scale
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window, nk=nk,
        scale=scale, kv_len=kv_len if kv_len is not None else T)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),    # acc (padded hd)
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
