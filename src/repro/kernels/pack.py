"""Pallas TPU fused wire pack/unpack: slot-table gather/scatter DMA.

The transport's payload layout (``PayloadSpec``) is a static table of
slots — for each travelling leaf, an element range ``[src_off, src_off +
size)`` of the raveled leaf and a destination range ``[dst_off, dst_off +
size)`` of the flat wire buffer. The XLA path materializes one sliced/cast
intermediate per leaf and concatenates them; these kernels instead move
every slot with DMAs inside a single grid program.

``gather_pack``   n raveled fp32 leaves -> (total,) flat wire buffer.
``scatter_unpack`` flat wire buffer + n raveled base leaves -> n updated
                  leaves; each output aliases its base in place
                  (``input_output_aliases``) and only the slot range is
                  written over it, so untouched elements (rows outside the
                  stage range) keep the receiver's values.

A TPU DMA moves whole (8, 128) fp32 tiles: HBM slices must start and end
on a tile boundary (Mosaic refuses e.g. a 192-element norm scale or a copy
to offset 1000). Slots have arbitrary lengths and offsets, so every buffer
is handed to the kernel as a (rows, 128) tile array, and each slot's source
is front-padded so that its range starts at the same in-tile position as
its destination (``_tile_rows``; a bitcast where the leaf is already
tile-aligned). Then each slot is

  - its whole destination tiles, one HBM->HBM DMA per slot, all in flight
    together;
  - at most two edge tiles (where the slot starts or ends mid-tile, shared
    with a neighbouring slot or the base's own values), merged one at a
    time through VMEM: source tile and destination tile in, lane/sublane
    mask select, tile back out.

Oracles: ``ref.wire_pack_ref`` / ``ref.wire_unpack_ref``; parity:
tests/test_kernels.py (interpret mode); v5e compile at ViT-Tiny slot
tables: tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WIRE_DTYPE = jnp.float32
ROWS, LANES = 8, 128
TILE = ROWS * LANES          # elements per (8, 128) fp32 tile: the DMA unit


def _tile_rows(v, front: int):
    """1-D ``v`` -> (rows, 128) with ``front`` leading pad elements, the
    length rounded up to whole tiles."""
    back = (-(front + v.shape[0])) % TILE
    if front or back:
        v = jnp.pad(v, (front, back))
    return v.reshape(-1, LANES)


def _slot_copy_kernel(*refs, n_in, table):
    """``table`` rows ``(src, src_off, dst, dst_off, size)`` index the
    input and output refs; ``src_off == dst_off (mod TILE)`` for every
    row."""
    ins, rest = refs[:n_in], refs[n_in:]
    outs, (sem, a_buf, b_buf, io_sem) = rest[:-4], rest[-4:]

    whole = []
    for k, (si, so, di, do, n) in enumerate(table):
        t0, t1 = -(-do // TILE), (do + n) // TILE
        if t1 > t0:
            st = t0 + (so - do) // TILE
            whole.append(pltpu.make_async_copy(
                ins[si].at[pl.ds(st * ROWS, (t1 - t0) * ROWS)],
                outs[di].at[pl.ds(t0 * ROWS, (t1 - t0) * ROWS)],
                sem.at[k]))
    for c in whole:
        c.start()
    for c in whole:
        c.wait()

    pos = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    for si, so, di, do, n in table:
        for t in sorted({do // TILE, (do + n - 1) // TILE}):
            lo = max(do - t * TILE, 0)            # slot's span in tile t
            hi = min(do + n - t * TILE, TILE)
            if lo == 0 and hi == TILE:
                continue                      # whole tile: moved above
            st = t + (so - do) // TILE
            dst = outs[di].at[pl.ds(t * ROWS, ROWS)]
            get_a = pltpu.make_async_copy(ins[si].at[pl.ds(st * ROWS, ROWS)],
                                          a_buf, io_sem.at[0])
            get_b = pltpu.make_async_copy(dst, b_buf, io_sem.at[1])
            get_a.start()
            get_b.start()
            get_a.wait()
            get_b.wait()
            b_buf[...] = jnp.where((pos >= lo) & (pos < hi), a_buf[...],
                                   b_buf[...])
            put = pltpu.make_async_copy(b_buf, dst, io_sem.at[0])
            put.start()
            put.wait()


def _slot_copy(ins, out_shapes, table, aliases, interpret):
    kernel = functools.partial(_slot_copy_kernel, n_in=len(ins),
                               table=tuple(table))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        in_specs=[any_spec] * len(ins),
        out_specs=[any_spec] * len(out_shapes),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.SemaphoreType.DMA((len(table),)),
                        pltpu.VMEM((ROWS, LANES), WIRE_DTYPE),
                        pltpu.VMEM((ROWS, LANES), WIRE_DTYPE),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(*ins)


def gather_pack(srcs, layout, total: int, *, interpret: bool = False):
    """``srcs``: 1D fp32 leaves, one per layout row; ``layout``: static
    ``((src_off, dst_off, size), ...)``. Returns the (total,) wire buffer."""
    assert len(srcs) == len(layout) and layout
    fronts = [(do - so) % TILE for so, do, _ in layout]
    ins = [_tile_rows(s, f) for s, f in zip(srcs, fronts)]
    table = [(i, so + f, 0, do, n)
             for i, ((so, do, n), f) in enumerate(zip(layout, fronts))]
    rows = -(-total // TILE) * ROWS
    (out,) = _slot_copy(ins, [jax.ShapeDtypeStruct((rows, LANES),
                                                   WIRE_DTYPE)],
                        table, {}, interpret)
    return out.reshape(-1)[:total]


def scatter_unpack(flat, bases, layout, *, interpret: bool = False):
    """Reverse of ``gather_pack``: write each slot range of ``flat`` over
    the matching range of its (aliased) 1D fp32 base leaf. Returns the
    updated leaves in layout order."""
    assert len(bases) == len(layout) and layout
    fronts = [(do - so) % TILE for so, do, _ in layout]
    tiled = [_tile_rows(b, f) for b, f in zip(bases, fronts)]
    table = [(0, do, i, so + f, n)
             for i, ((so, do, n), f) in enumerate(zip(layout, fronts))]
    outs = _slot_copy([_tile_rows(flat, 0)] + tiled,
                      [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiled],
                      table, {i + 1: i for i in range(len(tiled))},
                      interpret)
    return [o.reshape(-1)[f:f + b.shape[0]]
            for o, f, b in zip(outs, fronts, bases)]
