"""The ViT family: federated MoCo v3 on the program's ViT encoder over
synthetic images.

What the harness asks of a family (``harness.load_family``), and the
configuration keys this family reads (``image_size``, ``patch_size``,
``model``, ``ssl``, ``train``; the traffic's ``pool``, ``num_classes``,
``aux``):

``program_configs(cfg, traffic)``
    the program's ``(ModelConfig, SSLConfig, TrainConfig)``;
``make_inputs(seed, traffic, cfg)``
    the run's inputs from the seed, on the device: an object with the
    clients' ``shards`` (host index arrays) and the run ``key``;
``run_kwargs(inputs, cfg)``
    the family's keyword arguments of ``run_fedssl``;
``reference_rounds(cfg, traffic, numerics, inputs, n)``
    rounds 0 to n-1 of the plain reference (``numerics`` is
    ``"reference"`` or ``"control"``): ``(initial online model,
    [(online model after round i, its mean last-step client loss, its
    first-gradient norms)])``;
``round_flops(cfg, traffic)``
    per round: ``client_samples``, ``client_flops``, ``calib_samples``,
    ``calib_flops``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax

from chipbench import data, flops, reference


class Inputs(NamedTuple):
    pool: jax.Array          # (pool, size, size, 3) images
    shards: list             # each client's pool indices (host arrays)
    aux: jax.Array | None    # the server's auxiliary images
    key: jax.Array           # the run key


def program_configs(cfg, traffic):
    from repro.configs.base import ModelConfig, SSLConfig, TrainConfig

    from chipbench.harness import BenchError
    if cfg["patch_size"] != 4:
        raise BenchError("the program's ViT path patchifies by 4")
    m = dict(cfg["model"])
    model = ModelConfig(arch_id=cfg["name"], family="dense", vocab_size=0,
                        causal=False, num_kv_heads=m["num_heads"], **m)
    ssl = SSLConfig(**cfg["ssl"])
    train = TrainConfig(batch_size=traffic["batch"], **cfg["train"])
    return model, ssl, train


def make_inputs(seed, traffic, cfg):
    inputs = Inputs(*data.make_inputs(seed, traffic, cfg["image_size"]))
    jax.block_until_ready(inputs.pool)
    return inputs


def run_kwargs(inputs, cfg):
    return {"images": inputs.pool, "aux_images": inputs.aux,
            "image_size": cfg["image_size"]}


def reference_rounds(cfg, traffic, numerics, inputs, n):
    ref = reference.RoundRef(cfg, traffic, reference.Numerics(numerics))
    return ref.rounds(inputs.key, inputs.pool, inputs.shards, inputs.aux, n)


round_flops = flops.round_flops
