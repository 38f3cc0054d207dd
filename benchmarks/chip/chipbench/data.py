"""Inputs made from the seed: the image pool, the client shards and the
server's auxiliary set.

The pool generator is the benchmark's own copy of the program's synthetic
CIFAR-sized textures (``repro.data.synthetic.synthetic_images``), so a
change to the program cannot change what the benchmark feeds it. The pool
is made on the device in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def run_key(seed: int):
    """PRNG key for any seed up to 64 bits: ``PRNGKey`` alone keeps only
    the low 32 bits when 64-bit mode is off, so the high bits are folded
    in."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@functools.partial(jax.jit, static_argnames=("n", "num_classes", "size"))
def synthetic_images(key, n: int, num_classes: int = 10, size: int = 32):
    """(n, size, size, 3) float32 images in [0, 1]: one procedural texture
    per class (frequency, orientation, colour) with a random phase and
    pixel noise."""
    kl, kp, kn = jax.random.split(key, 3)
    labels = jax.random.randint(kl, (n,), 0, num_classes)
    freqs = 1.0 + jnp.arange(num_classes, dtype=jnp.float32) % 5
    orient = (jnp.arange(num_classes, dtype=jnp.float32)
              * (np.pi / num_classes))
    colors = jax.random.uniform(jax.random.PRNGKey(7),
                                (num_classes, 3), minval=0.2, maxval=1.0)
    yy, xx = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                          jnp.arange(size, dtype=jnp.float32), indexing="ij")

    def one(label, phase, noise):
        f, th = freqs[label], orient[label]
        wave = jnp.sin(2 * np.pi * f / size *
                       (xx * jnp.cos(th) + yy * jnp.sin(th)) + phase)
        base = 0.5 + 0.35 * wave
        img = base[..., None] * colors[label][None, None, :]
        return jnp.clip(img + 0.08 * noise, 0.0, 1.0)

    phases = jax.random.uniform(kp, (n,), maxval=2 * np.pi)
    noise = jax.random.normal(kn, (n, size, size, 3))
    return jax.vmap(one)(labels, phases, noise)


def make_inputs(seed: int, traffic: dict, image_size: int):
    """(pool, client shards, aux images or None, run key) for one run.

    Every seed gets the same sizes: ``pool`` images split IID into
    ``clients`` equal shards, and ``aux`` server images drawn without
    replacement from the pool; only which images and their order change.
    """
    key = run_key(seed)
    k_pool, k_run = jax.random.split(key)
    n, clients = traffic["pool"], traffic["clients"]
    if traffic["partition"] != "iid" or n % clients:
        raise ValueError("traffic needs an IID partition into equal shards")
    pool = synthetic_images(k_pool, n, traffic["num_classes"], image_size)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    shards = [np.sort(s) for s in np.split(perm, clients)]
    aux = None
    if traffic.get("aux", 0):
        aux = pool[jnp.asarray(np.sort(rng.choice(n, traffic["aux"],
                                                  replace=False)))]
    return pool, shards, aux, k_run
