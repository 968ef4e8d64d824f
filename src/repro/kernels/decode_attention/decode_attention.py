"""Pallas TPU decode-attention kernel: one query token against a long KV
cache (flash-decoding style).

Grid = (batch, kv_heads, kv_blocks), kv innermost/sequential; the (m, l, acc)
online-softmax state lives in VMEM scratch.  The query block holds the G =
H/Kv query heads that share one KV head, so GQA needs no KV repetition.
Cache slots carry their absolute position (`k_pos`); slots that are empty
(pos < 0), in the future (pos > q_pos), or outside the sliding window are
masked — exactly the ring-cache semantics of ``models.blocks``.

The same per-shard (m, l, acc) math backs the sequence-parallel distributed
decode path (DESIGN.md §6): each shard runs this kernel over its KV slice and
the partial results combine with a 3-float logsumexp reduction per head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def padded_cache_len(n: int, block_kv: int = DEFAULT_BLOCK_KV) -> int:
    """Smallest cache length >= n that :func:`decode_attention` never pads.

    The kernel tiles the KV axis by ``min(block_kv, S)``; any S above
    ``block_kv`` that is not a multiple of it forces a ``jnp.pad`` of K/V
    (a full cache copy) on *every* decode call.  Sizing the cache with this
    helper at engine init moves that cost to allocation time, once."""
    if n <= block_kv:
        return n
    return -(-n // block_kv) * block_kv


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, window: int, block_kv: int):
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # [G, d]
    k = k_ref[0, 0].astype(jnp.float32)           # [bkv, d]
    v = v_ref[0, 0].astype(jnp.float32)
    k_pos = kpos_ref[0]                           # [1, bkv]
    q_pos = qpos_ref[pl.program_id(0)]            # scalar int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s *= q.shape[-1] ** -0.5                      # [G, bkv]

    valid = (k_pos >= 0) & (k_pos <= q_pos)
    if window > 0:
        valid &= (q_pos - k_pos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur[:, None]), 0.0)
    l_cur = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.where(l_scr[:, 0] == 0.0, 1.0, l_scr[:, 0])
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_kv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_pos: jax.Array, q_pos: jax.Array, *,
                     window: int = 0, block_kv: int = DEFAULT_BLOCK_KV,
                     interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k, v: [B, Kv, S, D]; k_pos: [B, S]; q_pos: [B] ->
    [B, H, D]."""
    b, h, d = q.shape
    kv_heads, s = k.shape[1], k.shape[2]
    g = h // kv_heads
    block_kv = min(block_kv, s)
    pad = (-s) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    sp = s + pad
    qg = q.reshape(b, kv_heads, g, d)
    # k_pos as [B, 1, S]: the (1, block_kv) trailing block then satisfies the
    # TPU tiling rule; q_pos is a per-row scalar, read from SMEM
    k_pos = k_pos.astype(jnp.int32)[:, None, :]
    q_pos = q_pos.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv_heads, sp // block_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, ki, qp: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ki, qp: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ki, qp: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b_, h_, ki, qp: (b_, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h_, ki, qp: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, block_kv=block_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, g, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(q_pos, qg, k, v, k_pos)
    return out.reshape(b, h, d)
