"""The pieces of a family's random weights (``bench/families/<family>.py``),
which it makes from ``--seed`` on the device in one jitted call, in the
type they are served in.

Every matrix is uniform in ``[-1, 1]`` over the square root of its fan-in;
norm scales are 1 and norm biases 0.  The plain reference reads the same
arrays."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low 32 bits make the key and
    the rest is folded in, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


def dense(key, shape, fan_in, dtype):
    x = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (x * fan_in ** -0.5).astype(dtype)


def norm(s, lead=()):
    """A norm's parameters: ``scale``, and a ``bias`` for a LayerNorm."""
    out = {"scale": jnp.ones(lead + (s.d_model,), s.dtype)}
    if s.norm == "layer":
        out["bias"] = jnp.zeros(lead + (s.d_model,), s.dtype)
    return out
