"""Random weights of a cell, made by the benchmark from ``--seed`` on the
device in one jitted call, in the type they are served in.

Every matrix is uniform in ``[-1, 1]`` over the square root of its fan-in,
norm scales are 1 and norm biases 0.  The tree is laid out as the serving
engine takes it: ``embed [V, d]``, ``head [d, V]``, ``ln_f``, and one
``groups`` entry whose leaves carry a leading layer axis.  The plain
reference (``reference.py``) reads the same arrays."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .shape import Shape


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


def _dense(key, shape, fan_in, dtype):
    x = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (x * fan_in ** -0.5).astype(dtype)


def _norm(s: Shape, lead=()):
    out = {"scale": jnp.ones(lead + (s.d_model,), s.dtype)}
    if s.norm == "layer":
        out["bias"] = jnp.zeros(lead + (s.d_model,), s.dtype)
    return out


def _layer(s: Shape, key) -> dict:
    d, h, kv, hd, f = s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff
    k = jax.random.split(key, 7)
    mlp = {"w_up": _dense(k[4], (d, f), d, s.dtype),
           "w_down": _dense(k[5], (f, d), f, s.dtype)}
    if s.gated:
        mlp["w_gate"] = _dense(k[6], (d, f), d, s.dtype)
    return {
        "ln1": _norm(s),
        "attn": {"wq": _dense(k[0], (d, h, hd), d, s.dtype),
                 "wk": _dense(k[1], (d, kv, hd), d, s.dtype),
                 "wv": _dense(k[2], (d, kv, hd), d, s.dtype),
                 "wo": _dense(k[3], (h, hd, d), h * hd, s.dtype)},
        "ln2": _norm(s),
        "mlp": mlp,
    }


def init_weights(s: Shape, seed: int) -> dict:
    """The whole tree, built by one jitted program; layers are made one at
    a time inside a scan, so no more than one layer's float32 temporaries
    are live."""

    @jax.jit
    def build(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)
        _, layers = jax.lax.scan(lambda c, k: (c, _layer(s, k)), None,
                                 jax.random.split(k_layers, s.layers))
        return {"embed": _dense(k_embed, (s.vocab, s.d_model), s.vocab,
                                s.dtype),
                "head": _dense(k_head, (s.d_model, s.vocab), s.d_model,
                               s.dtype),
                "ln_f": _norm(s),
                "groups": [layers]}

    return build(seed_key(seed))
