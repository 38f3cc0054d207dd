"""Compiles for a described TPU v5e: the main path's programs at real size.

Nothing here runs on a chip. Each test lowers a program with shapes only
and compiles it with the TPU compiler for one chip of a described
``v5e:2x2`` topology, so a kernel that Mosaic refuses (an unaligned DMA, a
primitive with no TPU lowering, a block over the VMEM budget) or a step
that does not fit the chip's HBM fails here, in CI, and not on the chip.

Shapes are the published ViT-Tiny with its MoCo v3 heads (the default of
``python -m repro.launch.train --mode vit``): the slot tables ``Transport``
builds for its ``e2e`` payload and for one layer-wise stage, the 4096x4096
projector weight, and one client's local step at batch 1024.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and pytest-xdist workers import
every test file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FLConfig
from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.federated import client as client_mod
from repro.federated import transport as tr
from repro.kernels import pack as pk
from repro.kernels import wire_codecs as wc
from repro.launch.train import vit_configs
from repro.optim import make_optimizer

HBM_BYTES = int(15.75 * 2 ** 30)      # what XLA lets one v5e program use
BATCH = 1024                          # TrainConfig default: the paper's


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _vec(sharding, n, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _published():
    cfg, ssl_cfg, tc = vit_configs()
    encoder = ssl_mod.make_vit_encoder(cfg, remat=tc.remat)
    state = jax.eval_shape(lambda k: ssl_mod.ssl_init(k, encoder, ssl_cfg),
                           jax.random.PRNGKey(0))
    return cfg, ssl_cfg, tc, encoder, state


def _payload_spec(which):
    """Upload spec of the e2e payload, or of lw_fedssl stage 6 of 12."""
    *_, state = _published()
    schedule = "e2e" if which == "e2e" else "lw_fedssl"
    plans = sched.build_schedule(FLConfig(schedule=schedule, rounds=12), 12)
    plan = plans[0] if which == "e2e" else plans[5]
    online = state["online"]
    return online, tr.Transport("fp32").plan_specs(online, plan)["upload"]


def test_published_config_is_vit_tiny_with_moco_heads():
    cfg, ssl_cfg, tc, _, state = _published()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff) == \
        (12, 192, 3, 768)
    assert (ssl_cfg.proj_hidden, ssl_cfg.pred_hidden, ssl_cfg.proj_dim) == \
        (4096, 4096, 256)
    assert tc.remat and tc.batch_size == BATCH
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state["online"]))
    assert 26_000_000 < n < 26_200_000, n


@pytest.mark.parametrize("which", ["e2e", "lw_stage6"])
def test_gather_pack_compiles(one_chip, which):
    online, spec = _payload_spec(which)
    layout = tr.slot_pack_layout(spec)
    assert any(n % pk.TILE or so % pk.TILE or do % pk.TILE
               for so, do, n in layout), "table should hold unaligned slots"
    srcs = [_vec(one_chip, int(np.prod(a.shape)))
            for a in tr._slot_leaves(online, spec)]
    jax.jit(lambda s: pk.gather_pack(s, layout, spec.total)).lower(
        srcs).compile()


@pytest.mark.parametrize("which", ["e2e", "lw_stage6"])
def test_scatter_unpack_compiles(one_chip, which):
    online, spec = _payload_spec(which)
    layout = tr.slot_pack_layout(spec)
    bases = [_vec(one_chip, int(np.prod(a.shape)))
             for a in tr._slot_leaves(online, spec)]
    jax.jit(lambda f, b: pk.scatter_unpack(f, b, layout)).lower(
        _vec(one_chip, spec.total), bases).compile()


def test_int8_quant_compiles_on_projector_weight(one_chip):
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=one_chip)
    jax.jit(wc.int8_quant_matrix).lower(x).compile()
    q = jax.ShapeDtypeStruct((4096, 4096), jnp.int8, sharding=one_chip)
    jax.jit(wc.int8_dequant_matrix).lower(q, _vec(one_chip, 4096)).compile()


def test_topk_kernels_compile_on_e2e_payload(one_chip):
    _, spec = _payload_spec("e2e")
    n = _vec(one_chip, spec.total)
    jax.jit(wc.compensate).lower(n, n, n).compile()
    jax.jit(wc.topk_ef_update).lower(
        n, _vec(one_chip, 1), _vec(one_chip, 1, jnp.int32)).compile()


def test_full_width_local_step_fits_one_chip(one_chip):
    """One client's e2e MoCo v3 step, ViT-Tiny at batch 1024 with remat:
    arguments plus temporaries must fit the chip's HBM."""
    _, ssl_cfg, tc, encoder, state = _published()
    opt = make_optimizer(tc)
    step = client_mod.make_local_step(encoder, ssl_cfg, opt, sub_layers=12,
                                      active_from=0, align=False,
                                      depth_dropout=0.0)
    opt_state = jax.eval_shape(opt.init, state["online"])
    images = jax.ShapeDtypeStruct((BATCH, 32, 32, 3), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    compiled = step.lower(*_on(one_chip, (state, opt_state, images, key,
                                          lr)), None).compile()
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used < HBM_BYTES, (mem.temp_size_in_bytes,
                              mem.argument_size_in_bytes)


def test_wire_codec_programs_compile_over_e2e_payload(one_chip):
    """The whole-payload programs ``--transport-kernels pallas`` runs: one
    int8 kernel per slot (per-channel and per-tensor slots alike) and the
    top-k encode with its error-feedback update."""
    from repro.kernels import ops
    _, spec = _payload_spec("e2e")
    segs, nscales = tr.int8_segs(spec)
    n = _vec(one_chip, spec.total)
    ops._int8_enc_call(segs, False).lower(n).compile()
    ops._int8_dec_call(segs, spec.total, False).lower(
        _vec(one_chip, spec.total, jnp.int8), _vec(one_chip, nscales)
    ).compile()
    k = tr.TopKCodec().k_for(spec)
    ops._topk_call(k, False).lower(n, n, n).compile()
