"""Vision Transformer backbone (ViT-Tiny) — the paper's encoder F.

Matches the paper's setup: 32x32x3 inputs, patch size 4 (trained patch
projection, per MoCo v3 deviation noted in the paper), learned positional
embeddings, CLS token, 12 blocks. The forward is ``vit_suffix`` over
``vit_prefix``, split at the layer-wise stage interface (``active_from``,
``sub_layers``) used by FedMoCo-LW / LW-FedSSL / Prog-FedSSL, so branches
that share the frozen prefix can compute it once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.layers.init import dense_init, embed_init
from repro.models.lm import _slice_stack, _stacked_init
from repro.models import scan_cfg


def num_patches(image_size: int, patch_size: int) -> int:
    return (image_size // patch_size) ** 2


def init_vit(key, cfg, image_size: int = 32, patch_size: int = 4):
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    n = num_patches(image_size, patch_size)
    return {
        "patch": dense_init(ks[0], (patch_size * patch_size * 3, cfg.d_model), dt),
        "pos": embed_init(ks[1], (n + 1, cfg.d_model), dt),
        "cls": embed_init(ks[2], (1, 1, cfg.d_model), dt),
        "blocks": _stacked_init(ks[3], cfg, "enc", cfg.num_layers),
        "final_ln": B.rmsnorm_init(cfg.d_model, dt),
    }


def patchify(images, patch_size: int):
    """images: (B, H, W, 3) -> (B, n_patches, P*P*3)."""
    Bsz, H, W, C = images.shape
    ph, pw = H // patch_size, W // patch_size
    x = images.reshape(Bsz, ph, patch_size, pw, patch_size, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(Bsz, ph * pw, patch_size * patch_size * C)


def _scan_blocks(params, x, cfg, lo: int, hi: int, layer_gates, remat):
    """Blocks ``[lo, hi)`` over ``x``; each gate multiplies its block's
    residual delta."""
    def body(carry, pg):
        x, _ = carry
        p, g = pg
        fn = functools.partial(B.block_apply, cfg=cfg, kind="enc")
        if remat:
            fn = jax.checkpoint(fn)
        x2, a = fn(p, x)
        x = x + g.astype(x.dtype) * (x2 - x)
        return (x, a), None

    gates = (jnp.ones((cfg.num_layers,), jnp.float32)
             if layer_gates is None else layer_gates)
    (x, _), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        (_slice_stack(params["blocks"], lo, hi), gates[lo:hi]),
        unroll=scan_cfg.scan_unroll())
    return x


def vit_prefix(params, images, cfg, *, patch_size: int = 4,
               active_from: int = 0, remat: bool = False, layer_gates=None):
    """Patch embedding, CLS and positions, then the frozen blocks
    ``[0, active_from)``: (B, tokens, d_model) activations that carry no
    gradient. Every branch whose prefix has these weights and gates can
    start its suffix from them."""
    x = patchify(images, patch_size).astype(jnp.dtype(cfg.param_dtype))
    x = x @ params["patch"]
    Bsz = x.shape[0]
    cls = jnp.broadcast_to(params["cls"], (Bsz, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"][None]
    if active_from > 0:
        with jax.named_scope("frozen"):
            x = _scan_blocks(params, x, cfg, 0, active_from, layer_gates,
                             remat)
            x = jax.lax.stop_gradient(x)
    return x


def vit_suffix(params, x, cfg, *, sub_layers=None, active_from: int = 0,
               remat: bool = False, layer_gates=None):
    """The blocks ``[active_from, sub_layers)`` over a prefix's
    activations, the final norm and the CLS read-out (B, d_model)."""
    sub = cfg.num_layers if sub_layers is None else sub_layers
    if sub > active_from:
        with jax.named_scope("trained"):
            x = _scan_blocks(params, x, cfg, active_from, sub, layer_gates,
                             remat)
    x = B.rmsnorm(params["final_ln"], x, cfg.norm_eps)
    return x[:, 0]
