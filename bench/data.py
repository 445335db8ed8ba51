"""Inputs made from the run's seed, on the device, in one jitted call each.

The paper trains on CIFAR-10; there is no network here, so the views are
synthetic in the way `repro/data/multiview.py` builds them (its structure,
not its code): ten smooth class prototypes, a smooth per-image deformation,
pixel noise, and J views of each image with additive Gaussian noise of the
node's sigma.  Every seed gives the same shapes and sizes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, *stream: int):
    """A threefry key for (seed, stream...): any whole number works, more
    than 32 bits included."""
    words = np.random.SeedSequence(
        [int(seed) % 2 ** 64, *stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _smooth_up(x, H, W):
    """(..., h, w, C) -> (..., H, W, C): nearest upsample, then two passes
    of a 3-wide box filter along each image axis."""
    h, w = x.shape[-3], x.shape[-2]
    up = jnp.repeat(jnp.repeat(x, H // h, axis=-3), W // w, axis=-2)
    for axis in (-3, -2):
        up = (jnp.roll(up, 1, axis) + up + jnp.roll(up, -1, axis)) / 3.0
    return up


@partial(jax.jit, static_argnames=("n", "num_classes", "image_shape",
                                   "noise_stds"))
def multiview(k, *, n: int, num_classes: int, image_shape, noise_stds):
    """(views (J, n, H, W, C) float32, labels (n,) int32)."""
    H, W, C = image_shape
    ks = jax.random.split(k, 5)
    protos = _smooth_up(jax.random.normal(ks[0], (num_classes, 4, 4, C)),
                        H, W)
    protos = protos / protos.std(axis=(1, 2, 3), keepdims=True)
    labels = jax.random.randint(ks[1], (n,), 0, num_classes, jnp.int32)
    images = protos[labels] + _smooth_up(
        0.6 * jax.random.normal(ks[2], (n, 4, 4, C)), H, W)
    images = images + 0.1 * jax.random.normal(ks[3], images.shape)
    images = (images - images.mean()) / images.std()
    stds = jnp.asarray(noise_stds, jnp.float32)[:, None, None, None, None]
    views = images[None] + stds * jax.random.normal(
        ks[4], (len(noise_stds),) + images.shape)
    return views, labels
