"""Inputs drawn from the seed, in the input quantizer's range."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int, stream: int):
    """A key per (seed, stream): all 64 bits of the seed are kept."""
    s = int(seed) % 2**64
    key = jax.random.wrap_key_data(np.array([s >> 32, s & 0xFFFFFFFF],
                                            np.uint32))
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnums=1)
def _uniform(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)


def images(seed: int, stream: int, n: int, shape) -> np.ndarray:
    """``n`` images, uniform in [-1, 1) (the 8-bit input quantizer's
    range), drawn on the default device in one jitted call and fetched to
    the host, where a scoring job's batches come from."""
    return np.asarray(_uniform(_key(seed, stream), (n,) + tuple(shape)))
