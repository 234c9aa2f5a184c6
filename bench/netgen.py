"""Seeds, for every configuration whatever its family: the run's root
key and its input images, made on the device.  The weights are the
family's own (``bench/families/<family>.py``, ``make_weights``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """PRNG key for any non-negative seed (64-bit seeds keep their high
    word)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def images(cfg: dict, key, n: int):
    """``n`` seeded float32 images in [0, 1)."""
    return jax.random.uniform(key, (n,) + tuple(cfg["input_hw"]),
                              jnp.float32)
