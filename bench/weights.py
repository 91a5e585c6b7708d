"""Seeded weights: a PRNG key from the seed, and the configuration's
family draws them on the device in one jitted call, in the layout the
program serves (``bench/reference/<family>.py``, ``make_weights``).
Imports nothing of the program."""

from __future__ import annotations

import jax

from bench.model import family


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (above 2**32 too)."""
    key = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, part)
    return key


def make_weights(spec, seed: int):
    """The configuration's weights for ``seed``, on the default device."""
    return family(spec.reference).make_weights(spec, seed_key(seed))
