"""Pallas TPU fused wire codecs: int8 per-channel quant + top-k EF update.

int8 (``int8_quant_matrix`` / ``int8_dequant_matrix``): the XLA codec path
runs a separate abs/max reduce, scale divide and round per slot, each
materializing intermediates. Here one kernel per slot matrix does the
whole thing in a single grid program: a two-phase sequential grid over row
tiles — phase 0 accumulates the per-column absmax into a persistent VMEM
scratch, phase 1 turns it into the dequant scale (``max(amax, 1e-12) /
127``) and emits the clipped/rounded int8 payload — so each element is
read exactly twice and written once, with no dense fp32 intermediates.
The grid walks column tiles outermost, so a block is at most (256, 512)
fp32 whatever the channel count (the 4096x4096 projector weight included)
and stays well inside the default scoped VMEM. The math is bit-identical
to ``transport.Int8Codec`` (same IEEE fp32 ops, round-half-even).

top-k (``compensate`` / ``topk_ef_update``): the XLA path materializes the
delta, the compensated delta, |delta| and the post-selection residual as
separate dense buffers. ``compensate`` fuses delta + error-feedback add +
|.| into one pass; ``topk_ef_update`` applies the residual update on-chip:
given the k-th magnitude threshold it zeroes every *selected* entry of the
compensated delta in one pass, using a sequential-grid running count so
``|x| == threshold`` ties are broken exactly like ``lax.top_k`` (lowest
index first, up to the ``needed`` count). The prefix counts of tied
entries are two triangular 0/1 matmuls on the MXU (Pallas TPU has no
``cumsum``); every operand is a small integer, so bf16 inputs with fp32
accumulation count exactly. What's left *is* the new error-feedback
residual — dropped mass, nothing else.

Oracles in ref.py; parity tests in tests/test_kernels.py (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _pad2(x, br, bc=LANE):
    """Pad (R, C) up to (multiple of br, multiple of bc)."""
    R, C = x.shape
    pr, pc = (-R) % br, (-C) % bc
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x, R, C


# ---------------------------------------------------------------------------
# int8 per-channel (per-column) symmetric quantization
# ---------------------------------------------------------------------------
def _int8_quant_kernel(x_ref, q_ref, s_ref, amax_ref):
    phase = pl.program_id(1)
    tile = pl.program_id(2)

    @pl.when((phase == 0) & (tile == 0))
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    x = x_ref[...]

    @pl.when(phase == 0)
    def _reduce():
        amax_ref[...] = jnp.maximum(
            amax_ref[...], jnp.max(jnp.abs(x), axis=0, keepdims=True))

    @pl.when(phase == 1)
    def _quantize():
        scale = jnp.maximum(amax_ref[...], 1e-12) / 127.0
        s_ref[...] = scale
        q_ref[...] = jnp.clip(jnp.round(x / scale),
                              -127, 127).astype(jnp.int8)


def int8_quant_matrix(x, *, br: int = 256, bc: int = 512,
                      interpret: bool = False):
    """x: (R, C) fp32 -> (q (R, C) int8, scale (C,) fp32), scale per column
    (``= max(absmax, 1e-12) / 127``), q = clip(round(x / scale)). Grid:
    (column tile, phase, row tile) — each column tile reduces its absmax
    over every row tile, then quantizes them."""
    bc = min(bc, -(-x.shape[1] // LANE) * LANE)
    xp, R, C = _pad2(x, br, bc)
    Rp, Cp = xp.shape
    q, s = pl.pallas_call(
        _int8_quant_kernel,
        grid=(Cp // bc, 2, Rp // br),
        in_specs=[pl.BlockSpec((br, bc), lambda j, p, i: (i, j))],
        out_specs=[
            pl.BlockSpec((br, bc), lambda j, p, i: (i, j)),
            pl.BlockSpec((1, bc), lambda j, p, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, Cp), jnp.int8),
            jax.ShapeDtypeStruct((1, Cp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xp)
    return q[:R, :C], s[0, :C]


def _int8_dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def int8_dequant_matrix(q, scale, *, br: int = 256,
                        interpret: bool = False):
    """q: (R, C) int8, scale: (C,) -> (R, C) fp32 in one fused pass."""
    qp, R, C = _pad2(q, br)
    Rp, Cp = qp.shape
    sp = jnp.pad(scale.reshape(1, -1), ((0, 0), (0, Cp - C)))
    out = pl.pallas_call(
        _int8_dequant_kernel,
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, Cp), lambda i: (i, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, Cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qp, sp)
    return out[:R, :C]


# ---------------------------------------------------------------------------
# top-k delta sparsification with on-chip error-feedback update
# ---------------------------------------------------------------------------
def _compensate_kernel(f_ref, r_ref, e_ref, c_ref, a_ref):
    c = f_ref[...] - r_ref[...] + e_ref[...]
    c_ref[...] = c
    a_ref[...] = jnp.abs(c)


def compensate(flat, ref, res, *, br: int = 256, interpret: bool = False):
    """Fused (flat - ref + res, |flat - ref + res|) over 1D fp32 buffers:
    the delta-vs-reference and error-feedback add in one elementwise pass,
    emitting the magnitudes the top-k selection ranks on."""
    n = flat.shape[0]
    cols = LANE
    rows = -(-n // cols)
    shape2 = (rows, cols)

    def as2d(v):
        return jnp.pad(v, (0, rows * cols - n)).reshape(shape2)

    f2, r2, e2 = as2d(flat), as2d(ref), as2d(res)
    f2, R, C = _pad2(f2, br)
    r2, _, _ = _pad2(r2, br)
    e2, _, _ = _pad2(e2, br)
    Rp, Cp = f2.shape
    c2, a2 = pl.pallas_call(
        _compensate_kernel,
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, Cp), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((br, Cp), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Rp, Cp), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(f2, r2, e2)
    return c2.reshape(-1)[:n], a2.reshape(-1)[:n]


def _ef_update_kernel(c_ref, a_ref, t_ref, k_ref, o_ref, cnt_ref):
    tile = pl.program_id(0)

    @pl.when(tile == 0)
    def _init():
        cnt_ref[0] = 0

    c = c_ref[...]
    a = a_ref[...]
    thresh = t_ref[0]
    needed = k_ref[0]
    gt = a > thresh
    eq = a == thresh
    # global row-major rank (1-based) of each ==threshold entry: within-row
    # inclusive prefix (eq @ upper-triangular ones) + exclusive prefix of
    # the per-row totals (strictly-lower-triangular ones @ totals) + the
    # running count carried across tiles in SMEM (the grid is sequential)
    br, cols = c.shape
    eqf = eq.astype(jnp.float32)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 1))
    row = jnp.dot(eqf, upper.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    row_tot = jnp.broadcast_to(row[:, cols - 1:], (br, cols))
    lower = (jax.lax.broadcasted_iota(jnp.int32, (br, br), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (br, br), 0))
    prior = jnp.dot(lower.astype(jnp.float32), row_tot,
                    preferred_element_type=jnp.float32)
    rank = (row + prior).astype(jnp.int32) + cnt_ref[0]
    selected = gt | (eq & (rank <= needed))
    o_ref[...] = jnp.where(selected, 0.0, c)
    cnt_ref[0] = cnt_ref[0] + jnp.sum(eq.astype(jnp.int32))


def topk_ef_update(comp, thresh, needed, *, br: int = 256,
                   interpret: bool = False):
    """New error-feedback residual in one pass: zero the selected entries
    of the compensated delta ``comp`` — everything with ``|x| > thresh``
    plus the lowest-indexed ``|x| == thresh`` entries up to ``needed``
    (exactly ``lax.top_k``'s tie order) — and keep the rest (the dropped
    mass). ``thresh`` is (1,) fp32, ``needed`` is (1,) int32."""
    n = comp.shape[0]
    cols = LANE
    rows = -(-n // cols)

    def as2d(v):
        return jnp.pad(v, (0, rows * cols - n)).reshape(rows, cols)

    c2, a2 = as2d(comp), as2d(jnp.abs(comp))
    c2, R, C = _pad2(c2, br)
    a2, _, _ = _pad2(a2, br)
    Rp, Cp = c2.shape
    out = pl.pallas_call(
        _ef_update_kernel,
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, Cp), lambda i: (i, 0)),
            pl.BlockSpec((br, Cp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((br, Cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(c2, a2, thresh.reshape(1), needed.reshape(1))
    return out.reshape(-1)[:n]
