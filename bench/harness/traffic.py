"""The one traffic generator: an FL cohort's local data sets, made on the
device in one jitted call from the seed and a traffic file's parameters.

Images are class templates plus pixel noise (CIFAR-10's shapes: 32x32x3,
10 classes); each client's labels follow its own Dirichlet(alpha) class
mix, the paper's non-IID split.  The work per round depends only on the
shapes, never on the pixel values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _clients(key, clients, per_client, classes, image_shape, alpha, noise):
    k_t, k_p, k_l, k_n = jax.random.split(key, 4)
    templates = jax.random.normal(k_t, (classes,) + image_shape, jnp.float32)
    mix = jax.random.dirichlet(k_p, jnp.full((classes,), alpha, jnp.float32),
                               (clients,))
    labels = jax.random.categorical(
        k_l, jnp.log(jnp.maximum(mix, 1e-30))[:, None, :],
        shape=(clients, per_client)).astype(jnp.int32)
    images = templates[labels] + noise * jax.random.normal(
        k_n, (clients, per_client) + image_shape, jnp.float32)
    return images, labels


def client_images(seed: int, traffic: dict):
    """-> (images (K, B, H, W, C) float32, labels (K, B) int32)."""
    return _clients(jax.random.fold_in(jax.random.PRNGKey(seed), 0xDA7A),
                    int(traffic['clients']), int(traffic['per_client']),
                    int(traffic['classes']), tuple(traffic['image_shape']),
                    float(traffic['dirichlet_alpha']),
                    float(traffic['pixel_noise']))
