"""Step-time PRNG policy.

The reference engine draws dropout/transform randomness from a per-thread
Mersenne generator (``caffe/src/caffe/common.cpp`` RNG) — cheap on CPU.
JAX's default threefry2x32 is counter-based and reproducible but costs
real VPU time per mask on TPU; the hardware RBG generator is the
TPU-native equivalent of "a fast local generator" with the same
functional-key API.  Training-step keys (dropout masks, crop/mirror
draws, stochastic pooling) use RBG on TPU; *initialization* keys stay
threefry everywhere so filler golden tests are backend-independent.

``SPARKNET_PRNG=threefry2x32|rbg`` overrides.
"""

from __future__ import annotations

import functools
import os

import jax


def _default_impl() -> str:
    impl = os.environ.get("SPARKNET_PRNG")
    if impl is None:
        impl = "rbg" if jax.default_backend() == "tpu" else "threefry2x32"
    return impl


def train_key(seed: int = 0) -> jax.Array:
    """A typed PRNG key for training-step randomness (see module doc)."""
    return jax.random.key(seed, impl=_default_impl())


@functools.lru_cache(maxsize=16)
def _cached_train_key(seed: int, impl: str) -> jax.Array:
    return jax.random.key(seed, impl=impl)


def default_train_key(seed: int = 0) -> jax.Array:
    """``train_key`` for the hot-loop *default-rng* paths
    (``trainer.round(..., rng=None)`` every round): the key is cached
    per (seed, impl), so the per-round scalar host->device transfer a
    fresh ``jax.random.key`` pays disappears —
    ``tests/test_parallel.py`` runs the round loops under
    ``jax_transfer_guard`` at ``disallow`` and a fresh key per round is
    exactly the class of silent implicit transfer it exists to catch.
    (Keys are never consumed in place — reusing the cached array is
    semantically identical to rebuilding it.)"""
    return _cached_train_key(int(seed), _default_impl())
