"""Compile every serving Pallas kernel for a described TPU v5e chip.

Interpret-mode parity tests cannot see what the TPU compiler refuses
(block shapes off the (8, 128) tiling, primitives Mosaic cannot lower), so
each kernel is compiled here at published widths in bf16 -- yi-6b's
attention (32/4 heads of 128, 16-token KV blocks), rwkv6-7b's 64-wide heads,
recurrentgemma-9b's 4096-wide recurrence -- for one chip of a ``v5e:2x2``
topology, without a chip, and must come out as a Mosaic kernel
(``tpu_custom_call``).  The topology is described inside a fixture, so no
module import touches the TPU library; the tests skip where it cannot be
described.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention)
from repro.kernels.rglru.rglru import rglru_scan_state
from repro.kernels.rwkv6.rwkv6 import rwkv6_scan_state
from repro.kernels.segment_attention.segment_attention import (
    paged_segment_attention, segment_attention)

H, KV, D, T = 32, 4, 128, 16     # yi-6b heads x head_dim, KV block tokens
B, M, NB = 4, 64, 256            # slots, table blocks per slot, store blocks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Programs compiled for a described chip are written to the persistent
    cache but cannot be read back without one: keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _names_kernel(text, name):
    """The device trace names a kernel's operation after its compiled
    custom call; the benchmark finds the serving kernels by these names."""
    assert re.search(rf"^\s*(ROOT )?%{name}(\.\d+)? = \S+ custom-call\(",
                     text, re.M), f"no custom call named {name}"


@pytest.mark.parametrize("p", [8, 256])
def test_segment_attention_flat(one_chip, p):
    n = B * 256 + p          # every slot's ring ++ the stream's own keys
    text = _compile(lambda q, k, v, qp, kp, qs, ks: segment_attention(
        q, k, v, qp, kp, qs, ks), one_chip,
        ((p, H, D), jnp.bfloat16), ((n, KV, D), jnp.bfloat16),
        ((n, KV, D), jnp.bfloat16), ((p,), jnp.int32), ((n,), jnp.int32),
        ((p,), jnp.int32), ((n,), jnp.int32))
    _names_kernel(text, "segment_attention")


@pytest.mark.parametrize("p", [8, 256])
def test_segment_attention_paged(one_chip, p):
    text = _compile(lambda q, k, v, bt, qp, qs: paged_segment_attention(
        q, k, v, bt, qp, qs), one_chip,
        ((p, H, D), jnp.bfloat16), ((NB, KV, T, D), jnp.bfloat16),
        ((NB, KV, T, D), jnp.bfloat16), ((B, M), jnp.int32),
        ((p,), jnp.int32), ((p,), jnp.int32))
    _names_kernel(text, "paged_segment_attention")


def test_paged_decode_attention(one_chip):
    text = _compile(lambda q, k, v, bt, qp: paged_decode_attention(
        q, k, v, bt, qp), one_chip,
        ((B, H, D), jnp.bfloat16), ((NB, KV, T, D), jnp.bfloat16),
        ((NB, KV, T, D), jnp.bfloat16), ((B, M), jnp.int32),
        ((B,), jnp.int32))
    _names_kernel(text, "paged_decode_attention")


def test_decode_attention(one_chip):
    s = M * T
    text = _compile(lambda q, k, v, kp, qp: decode_attention(q, k, v, kp, qp),
             one_chip,
             ((B, H, D), jnp.bfloat16), ((B, KV, s, D), jnp.bfloat16),
             ((B, KV, s, D), jnp.bfloat16), ((B, s), jnp.int32),
             ((B,), jnp.int32))
    _names_kernel(text, "decode_attention")


def test_rwkv6_scan_state(one_chip):
    bh, s, n = B * 64, 64, 64            # rwkv6-7b: 64 heads of 64
    _compile(lambda r, k, v, w, u, s0: rwkv6_scan_state(r, k, v, w, u, s0),
             one_chip,
             ((bh, s, n), jnp.bfloat16), ((bh, s, n), jnp.bfloat16),
             ((bh, s, n), jnp.bfloat16), ((bh, s, n), jnp.float32),
             ((bh, n), jnp.float32), ((bh, n, n), jnp.float32))


def test_rglru_scan_state(one_chip):
    s, f = 128, 4096                     # recurrentgemma-9b: width 4096
    _compile(lambda a, b, h0: rglru_scan_state(a, b, h0), one_chip,
             ((B, s, f), jnp.bfloat16), ((B, s, f), jnp.bfloat16),
             ((B, f), jnp.float32))
