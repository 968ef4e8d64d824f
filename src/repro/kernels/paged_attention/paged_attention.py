"""Pallas TPU paged decode-attention kernel: one query token against K/V
scattered across a physical block store, gathered through per-sequence block
tables (vLLM-style paged KV cache).

Layout / ABI (shared with ``repro.serve.paging`` and ``models.blocks``):

  * block store   ``k_store, v_store: [num_blocks, kv_heads, T, head_dim]``
    — the single physical HBM allocation all sequences share; ``T`` is the
    block token granularity (``KVBlockPool.block_tokens``).
  * block table   ``block_tables: [B, max_blocks_per_seq] int32`` — entry
    ``i`` of row ``b`` names the physical block holding that row's logical
    tokens ``[i*T, (i+1)*T)``; ``-1`` marks an unallocated table slot.
  * logical position ``p`` of row ``b`` therefore lives at
    ``store[block_tables[b, p // T], :, p % T]``.

Grid = (batch, kv_heads, max_blocks_per_seq) with the block-table axis
innermost/sequential; the (m, l, acc) online-softmax state lives in VMEM
scratch exactly as in ``decode_attention``.  The block table and the
per-row query positions are scalar-prefetch operands (SMEM), so each K/V
block's DMA is issued from ``block_tables[b, i]`` *before* the kernel body
runs — the gather is free, no dense [B, S] cache is ever materialized.  Invalid table entries (-1) are
clamped to block 0 for the DMA and fully masked in the body.

Unlike the dense kernel there is no ``k_pos`` operand: positions are
implied by table order (slot ``i`` covers ``[i*T, (i+1)*T)``), and validity
is ``entry >= 0 and pos <= q_pos`` (plus the sliding window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, window: int, block_tokens: int):
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_i = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # [G, d]
    k = k_ref[0, 0].astype(jnp.float32)              # [T, d]
    v = v_ref[0, 0].astype(jnp.float32)
    entry = bt_ref[b, i]                             # scalar int32
    q_pos = qpos_ref[b]                              # scalar int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s *= q.shape[-1] ** -0.5                         # [G, T]

    # logical positions covered by table slot i (2-D iota for TPU)
    k_pos = i * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_tokens), 1)             # [1, T]
    valid = (entry >= 0) & (k_pos <= q_pos)
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

    @pl.when(i == n_i - 1)
    def _finish():
        denom = jnp.where(l_scr[:, 0] == 0.0, 1.0, l_scr[:, 0])
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_store: jax.Array,
                           v_store: jax.Array, block_tables: jax.Array,
                           q_pos: jax.Array, *, window: int = 0,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k_store/v_store: [N, Kv, T, D]; block_tables: [B, M]
    int32 (-1 = unallocated); q_pos: [B] -> [B, H, D]."""
    b, h, d = q.shape
    n_blocks, kv_heads, t, _ = k_store.shape
    m = block_tables.shape[1]
    g = h // kv_heads
    qg = q.reshape(b, kv_heads, g, d)
    q_pos = q_pos.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def kv_map(b_, h_, i_, bt, qp):
        # -1 entries are clamped to a real block for the DMA; the body
        # masks them out entirely via `entry >= 0`
        return (jnp.clip(bt[b_, i_], 0, n_blocks - 1), h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv_heads, m),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h_, i_, bt, qp: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, t, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h_, i_, bt, qp: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, block_tokens=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, q_pos, qg, k_store, v_store)
    return out.reshape(b, h, d)
