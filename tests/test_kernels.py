"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle.

The ``wire_*`` tests parametrize over the dispatch modes available on CPU
— ``None`` (platform default: the hostwire numpy engine, or Pallas
interpret when ``REPRO_WIRE_INTERPRET`` is set, as in the CI kernels job)
and ``True`` (Pallas interpret, always). Host mode is held to bit-exact
parity with the eager XLA oracles; interpret mode gets a one-quantum
int8 allowance because the Pallas interpreter lowers fp32 division as
reciprocal-multiply (1 ulp off IEEE), which can flip a rounded value.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import info_nce
from repro.kernels import ops, ref
from repro.kernels import wire_codecs as wc


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,S,T,Hq,Hkv,hd,causal,window,dtype", [
    (2, 128, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 256, 256, 4, 4, 128, True, 0, jnp.float32),
    (2, 128, 128, 8, 1, 64, False, 0, jnp.float32),
    (1, 200, 200, 4, 2, 48, True, 0, jnp.float32),   # unaligned (padding)
    (1, 384, 384, 2, 2, 96, True, 64, jnp.float32),  # sliding window
    (1, 256, 256, 4, 2, 64, True, 0, jnp.bfloat16),
    (1, 128, 128, 4, 4, 64, False, 0, jnp.bfloat16),
])
def test_flash_attention(B, S, T, Hq, Hkv, hd, causal, window, dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, S, Hq, hd), dtype)
    k = jax.random.normal(k2, (B, T, Hkv, hd), dtype)
    v = jax.random.normal(k3, (B, T, Hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    want = ref.sdpa_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal,
                        window=window).transpose(0, 2, 1, 3)
    assert out.shape == want.shape and out.dtype == q.dtype
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - want.astype(jnp.float32)))
    assert err < _tol(dtype), float(err)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 4, 64, 64, 128),
    (1, 128, 2, 32, 16, 64),
    (1, 512, 8, 64, 64, 128),
])
def test_ssd_scan(B, S, H, P, N, chunk, rng):
    k = jax.random.split(rng, 5)
    xh = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    a = -dt * jnp.exp(jax.random.normal(k[2], (H,))) * 0.1
    Bm = jax.random.normal(k[3], (B, S, N))
    Cm = jax.random.normal(k[4], (B, S, N))
    out = ops.ssd_scan(xh, dt, a, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(xh, dt, a, Bm, Cm, chunk=chunk)
    assert jnp.max(jnp.abs(out - want)) < 5e-3


def test_ssd_scan_matches_model_layer(rng):
    """Kernel agrees with the Mamba2 layer's internal chunked scan."""
    from repro.models.layers.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 256, 4, 32, 16
    k = jax.random.split(rng, 5)
    xh = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,))) * 0.1
    Bm = jax.random.normal(k[3], (B, S, N))
    Cm = jax.random.normal(k[4], (B, S, N))
    want, _ = ssd_chunked(xh, dt, A, Bm, Cm, 128)
    got = ops.ssd_scan(xh, dt, dt * A, Bm, Cm, chunk=128, interpret=True)
    assert jnp.max(jnp.abs(got - want)) < 5e-3


@pytest.mark.parametrize("B,d", [(128, 64), (256, 128), (384, 96)])
@pytest.mark.parametrize("tau", [0.2, 1.0])
def test_fused_info_nce(B, d, tau, rng):
    k1, k2 = jax.random.split(rng)
    q = jax.random.normal(k1, (B, d))
    k = jax.random.normal(k2, (B, d))
    got = ops.fused_info_nce(q, k, tau, interpret=True)
    want = info_nce(q, k, tau)
    assert abs(float(got) - float(want)) < 1e-4


@pytest.mark.parametrize("shape", [(256, 128), (4, 96, 256), (2, 3, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rmsnorm(shape, dtype, rng):
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, shape, dtype)
    s = 1.0 + 0.1 * jax.random.normal(k2, (shape[-1],))
    got = ops.fused_rmsnorm(x, s, interpret=True)
    want = ref.rmsnorm_ref(x.reshape(-1, shape[-1]), s).reshape(shape)
    err = jnp.max(jnp.abs(got.astype(jnp.float32)
                          - want.astype(jnp.float32)))
    assert err < _tol(dtype)


# ---------------------------------------------------------------------------
# wire kernels (transport fast path): host / interpret engines vs oracle
# ---------------------------------------------------------------------------
WIRE_MODES = (None, True)


def _exact(interpret) -> bool:
    """Host mode is bit-exact vs the eager oracles; interpret mode gets
    the one-quantum int8 allowance (see module docstring)."""
    return ops._wire_mode(interpret) == "host"


def _wire_leaves(rng):
    """Three leaves + a layout mixing full slots and a partial (stacked
    stage range) slot, with deliberately unaligned sizes."""
    k = jax.random.split(rng, 3)
    leaves = [jax.random.normal(k[0], (4, 33)),        # stacked, partial
              jax.random.normal(k[1], (129,)),
              jax.random.normal(k[2], (7, 5))]
    # rows: (src_off, dst_off, size); leaf 0 ships rows 1..3 only
    layout = ((33, 0, 66), (0, 66, 129), (0, 195, 35))
    total = 230
    return leaves, layout, total


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_pack_matches_ref(interpret, rng):
    leaves, layout, total = _wire_leaves(rng)
    got = np.asarray(ops.wire_pack(leaves, layout, total,
                                   interpret=interpret))
    want = np.asarray(ref.wire_pack_ref(
        [l.reshape(-1) for l in leaves], layout, total))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_unpack_matches_ref_and_roundtrips(interpret, rng):
    leaves, layout, total = _wire_leaves(rng)
    flat = jax.random.normal(jax.random.split(rng)[0], (total,))
    bases = [l.reshape(-1) for l in leaves]
    lay4 = tuple((s, d, n, n == b.shape[0])
                 for (s, d, n), b in zip(layout, bases))
    got = ops.wire_unpack(flat, bases, lay4, interpret=interpret)
    want = ref.wire_unpack_ref(jnp.asarray(flat), bases, layout)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    # pack(unpack(flat)) restores the wire buffer exactly
    repacked = ops.wire_pack(got, layout, total, interpret=interpret)
    assert np.array_equal(np.asarray(repacked), np.asarray(flat))


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_cast_roundtrip(interpret, rng):
    flat = jax.random.normal(rng, (517,))
    for dtype in (jnp.float16, jnp.bfloat16):
        wire = ops.wire_cast_encode(flat, dtype, interpret=interpret)
        want = np.asarray(flat.astype(dtype))
        assert np.array_equal(np.asarray(wire), want)
        dec = ops.wire_cast_decode(wire, interpret=interpret)
        assert np.array_equal(np.asarray(dec),
                              np.asarray(want.astype(np.float32)))


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_int8_matches_codec_math(interpret, rng):
    # two payload slots: a (64, 8) matrix (per-column scales) and a
    # 40-vector (single per-tensor scale)
    k1, k2 = jax.random.split(rng)
    a = jax.random.normal(k1, (64, 8)) * 3.0
    b = jax.random.normal(k2, (40,))
    flat = jnp.concatenate([a.reshape(-1), b])
    segs = ((0, 512, 8, 0), (512, 40, 1, 8))
    q, scales = ops.wire_int8_encode(flat, segs, 9, interpret=interpret)
    qa, sa = ref.int8_quant_ref(a)
    qb, sb = ref.int8_quant_ref(b.reshape(-1, 1))
    want_q = np.concatenate([np.asarray(qa).reshape(-1),
                             np.asarray(qb).reshape(-1)])
    want_s = np.concatenate([np.asarray(sa), np.asarray(sb)])
    if _exact(interpret):
        assert np.array_equal(np.asarray(q), want_q)
        assert np.array_equal(np.asarray(scales), want_s)
    else:
        assert np.abs(np.asarray(q).astype(np.int32)
                      - want_q.astype(np.int32)).max() <= 1
        np.testing.assert_allclose(np.asarray(scales), want_s, rtol=1e-6)
    dec = ops.wire_int8_decode(q, scales, segs, 552, interpret=interpret)
    want_dec = np.concatenate([
        np.asarray(ref.int8_dequant_ref(qa, sa)).reshape(-1),
        np.asarray(ref.int8_dequant_ref(qb, sb)).reshape(-1)])
    atol = 0.0 if _exact(interpret) else float(want_s.max()) * 1.01
    np.testing.assert_allclose(np.asarray(dec), want_dec, atol=atol)


@pytest.mark.parametrize("interpret", WIRE_MODES)
@pytest.mark.parametrize("with_res", [False, True])
def test_wire_topk_ef_matches_ref(interpret, with_res, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    n, k = 700, 70
    flat = jax.random.normal(k1, (n,))
    base = jax.random.normal(k2, (n,))
    res = jax.random.normal(k3, (n,)) * 0.1 if with_res else None
    idx, val, new_res = ops.wire_topk_encode_ef(flat, base, res, k,
                                                interpret=interpret)
    ridx, rval, rres, rdec = ref.topk_ef_ref(
        flat, base, jnp.zeros_like(flat) if res is None else res, k)
    # idx order is backend-specific (magnitude-sorted vs position-sorted):
    # the selected set, decoded payload and residual must match exactly
    assert sorted(np.asarray(idx).tolist()) == \
        sorted(np.asarray(ridx).tolist())
    dec = ops.wire_topk_decode(idx, val, n, interpret=interpret)
    assert np.array_equal(np.asarray(dec), np.asarray(rdec))
    assert np.array_equal(np.asarray(new_res), np.asarray(rres))


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_topk_breaks_ties_like_top_k(interpret):
    # exact duplicated magnitudes straddling the threshold: selection must
    # keep lax.top_k's lowest-index-first tie order
    flat = jnp.asarray(
        np.tile(np.asarray([5.0, -3.0, 3.0, 1.0, 3.0, -5.0], np.float32),
                40))
    base = jnp.zeros_like(flat)
    k = 100          # 80 entries of |x|=5, threshold ties at |x|=3
    idx, val, new_res = ops.wire_topk_encode_ef(flat, base, None, k,
                                                interpret=interpret)
    ridx, rval, rres, rdec = ref.topk_ef_ref(flat, base,
                                             jnp.zeros_like(flat), k)
    assert sorted(np.asarray(idx).tolist()) == \
        sorted(np.asarray(ridx).tolist())
    dec = ops.wire_topk_decode(idx, val, flat.shape[0],
                               interpret=interpret)
    assert np.array_equal(np.asarray(dec), np.asarray(rdec))
    assert np.array_equal(np.asarray(new_res), np.asarray(rres))


def _multi_tile_leaves(rng):
    """Slots spanning several (8, 128) tiles, at offsets and lengths that
    are not tile multiples, plus slots smaller than one tile that share
    tiles with their neighbours."""
    k = jax.random.split(rng, 4)
    leaves = [jax.random.normal(k[0], (3, 1000)),      # stacked, rows 1..2
              jax.random.normal(k[1], (5000,)),
              jax.random.normal(k[2], (192,)),
              jax.random.normal(k[3], (4103,))]
    layout = ((1000, 0, 2000), (0, 2000, 5000), (0, 7000, 192),
              (0, 7192, 4103))
    return leaves, layout, 11295


@pytest.mark.parametrize("interpret", WIRE_MODES)
def test_wire_pack_unpack_multi_tile(interpret, rng):
    leaves, layout, total = _multi_tile_leaves(rng)
    bases = [l.reshape(-1) for l in leaves]
    got = ops.wire_pack(leaves, layout, total, interpret=interpret)
    want = ref.wire_pack_ref(bases, layout, total)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    flat = jax.random.normal(jax.random.split(rng)[1], (total,))
    lay4 = tuple((s, d, n, n == b.shape[0])
                 for (s, d, n), b in zip(layout, bases))
    outs = ops.wire_unpack(flat, bases, lay4, interpret=interpret)
    for g, w in zip(outs, ref.wire_unpack_ref(flat, bases, layout)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_int8_quant_column_tiles_match_codec_math(rng):
    # three column tiles of 512 (the last one padded) and a padded row tile
    x = jax.random.normal(rng, (300, 1100)) * 2.0
    q, s = wc.int8_quant_matrix(x, interpret=True)
    wq, ws = ref.int8_quant_ref(x)
    assert q.shape == wq.shape and s.shape == ws.shape
    assert np.abs(np.asarray(q).astype(np.int32)
                  - np.asarray(wq).astype(np.int32)).max() <= 1
    np.testing.assert_allclose(np.asarray(s), np.asarray(ws), rtol=1e-6)


def test_topk_ef_update_ties_across_row_tiles():
    # 3 row tiles of 256 x 128: tied magnitudes in every tile, so the
    # running count must carry the tie rank from tile to tile
    flat = jnp.asarray(np.tile(np.asarray([2.0, -1.0, 1.0, 0.5],
                                          np.float32), 20000))
    base = jnp.zeros_like(flat)
    k = 30000        # 20000 entries of |x|=2, 10000 of the 40000 ties at 1
    idx, _, new_res = ops.wire_topk_encode_ef(flat, base, None, k,
                                              interpret=True)
    ridx, _, rres, _ = ref.topk_ef_ref(flat, base, jnp.zeros_like(flat), k)
    assert sorted(np.asarray(idx).tolist()) == \
        sorted(np.asarray(ridx).tolist())
    assert np.array_equal(np.asarray(new_res), np.asarray(rres))
