"""Pallas TPU fused segment-attention kernels: one packed query stream
against segment-tagged keys, without ever materializing the ``[H, P, N]``
score matrix.

Two entry points share one online-softmax body structure:

  * :func:`segment_attention` — keys are a flat axis carrying per-key
    ``(k_pos, k_seg)`` tags: the dense packed path's flattened all-slot ring
    view ++ in-stream keys.  Grid = (heads, q_tiles, k_tiles), k innermost /
    sequential; the (m, l, acc) state lives in VMEM scratch per q tile.
  * :func:`paged_segment_attention` — keys live in the paged block store and
    are gathered through per-slot block tables consumed as a
    **scalar-prefetch** operand (like ``kernels/paged_attention``): grid =
    (heads, q_tiles, B * max_blocks_per_seq), each K/V block's DMA issued
    from ``block_tables[j // M, j % M]`` before the body runs.  Key
    positions are implied by table order, key segments by table row, so no
    ``[B, M*T]`` logical view is ever materialized.

The same-segment / written / causal / window predicate is fused into the
tile mask (the packed-segment ABI of ``models.layers.segment_attention``),
and tiles the predicate fully masks — a decode rider's q tile against
another slot's keys, the common case once decode segments share the stream
— skip their matmul entirely (an exact no-op for the online softmax), so
key work stays proportional to the live predicate.
GQA is handled by gridding over *query* heads and mapping each to its KV
head (``h // group``), so no K/V repetition happens.  Fully-masked queries
(dead pad lanes, ``q_seg < 0``) finish with ``l == 0`` and emit exact
zeros — bit-identical to the ref oracle on every lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 256


def _online_update(s, valid, v, m_scr, l_scr, acc_scr):
    """One online-softmax tile update over scores ``s`` [bq, bk]."""
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


def _finish(o_ref, l_scr, acc_scr):
    # fully-masked rows keep l == 0: emit exact zeros (dead pad lanes)
    denom = jnp.where(l_scr[:, 0] == 0.0, 1.0, l_scr[:, 0])
    o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref,
            o_ref, m_scr, l_scr, acc_scr, *, window: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qp = qpos_ref[0][:, None]                         # [bq, 1]
    qs = qseg_ref[0][:, None]
    kp = kpos_ref[0][None, :]                         # [1, bk]
    ks = kseg_ref[0][None, :]
    valid = (ks == qs) & (qs >= 0) & (kp >= 0) & (kp <= qp)
    if window > 0:
        valid &= (qp - kp) < window

    # fully-masked (q_tile, k_tile) pairs — e.g. a decode rider's tile
    # against another slot's ring — are an exact no-op for the online
    # softmax (p = 0, m/l/acc unchanged): skip their matmul entirely, so
    # per-segment key work stays proportional to the live predicate
    @pl.when(valid.any())
    def _update():
        q = q_ref[0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= q.shape[-1] ** -0.5                      # [bq, bk]
        _online_update(s, valid, v, m_scr, l_scr, acc_scr)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def segment_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array, q_seg: jax.Array,
                      k_seg: jax.Array, *, window: int = 0,
                      block_q: int = DEFAULT_BLOCK_Q,
                      block_k: int = DEFAULT_BLOCK_K,
                      interpret: bool = False) -> jax.Array:
    """q: [P, H, D]; k, v: [N, Kv, D]; q_pos/q_seg: [P]; k_pos/k_seg: [N]
    -> [P, H, D]."""
    p, h, d = q.shape
    n, kvh, _ = k.shape
    g = h // kvh
    block_q = min(block_q, p)
    block_k = min(block_k, n)
    pad_q = (-p) % block_q
    pad_k = (-n) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q))
        q_seg = jnp.pad(q_seg, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_k), constant_values=-1)
        k_seg = jnp.pad(k_seg, (0, pad_k), constant_values=-1)
    pp, nn = p + pad_q, n + pad_k

    qt = jnp.swapaxes(q, 0, 1)                        # [H, P, D]
    kt = jnp.swapaxes(k, 0, 1)                        # [Kv, N, D]
    vt = jnp.swapaxes(v, 0, 1)

    grid = (h, pp // block_q, nn // block_k)
    out = pl.pallas_call(
        functools.partial(_kernel, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h_, qi, ki: (h_, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda h_, qi, ki: (h_ // g, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda h_, qi, ki: (h_ // g, ki, 0)),
            pl.BlockSpec((1, block_q), lambda h_, qi, ki: (0, qi)),
            pl.BlockSpec((1, block_q), lambda h_, qi, ki: (0, qi)),
            pl.BlockSpec((1, block_k), lambda h_, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_k), lambda h_, qi, ki: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda h_, qi, ki: (h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, pp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="segment_attention",
    )(qt, kt, vt, q_pos.astype(jnp.int32)[None], q_seg.astype(jnp.int32)[None],
      k_pos.astype(jnp.int32)[None], k_seg.astype(jnp.int32)[None])
    return jnp.swapaxes(out, 0, 1)[:p]


def _paged_kernel(bt_ref, q_ref, k_ref, v_ref, qpos_ref, qseg_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, window: int,
                  block_tokens: int, blocks_per_seq: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    slot = j // blocks_per_seq                        # key segment id
    entry = bt_ref[slot, j % blocks_per_seq]          # scalar int32

    # logical positions covered by table slot (2-D iota for TPU)
    kp = (j % blocks_per_seq) * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_tokens), 1)              # [1, T]
    qp = qpos_ref[0][:, None]                         # [bq, 1]
    qs = qseg_ref[0][:, None]
    valid = (entry >= 0) & (qs == slot) & (qs >= 0) & (kp <= qp)
    if window > 0:
        valid &= (qp - kp) < window

    # blocks owned by a slot no query in this tile belongs to (the common
    # case once decode riders share the stream) are an exact no-op: skip
    # the matmul, leaving the (m, l, acc) state untouched
    @pl.when(valid.any())
    def _update():
        q = q_ref[0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [T, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= q.shape[-1] ** -0.5                      # [bq, T]
        _online_update(s, valid, v, m_scr, l_scr, acc_scr)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "block_q",
                                             "interpret"))
def paged_segment_attention(q: jax.Array, k_store: jax.Array,
                            v_store: jax.Array, block_tables: jax.Array,
                            q_pos: jax.Array, q_seg: jax.Array, *,
                            window: int = 0, block_q: int = DEFAULT_BLOCK_Q,
                            interpret: bool = False) -> jax.Array:
    """q: [P, H, D]; k_store/v_store: [N, Kv, T, D]; block_tables: [B, M]
    int32 (-1 = unallocated, clamped for the DMA and masked in the body);
    q_pos/q_seg: [P] (segment id == block-table row) -> [P, H, D]."""
    p, h, d = q.shape
    n_blocks, kvh, t, _ = k_store.shape
    b, m = block_tables.shape
    g = h // kvh
    block_q = min(block_q, p)
    pad_q = (-p) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q))
        q_seg = jnp.pad(q_seg, (0, pad_q), constant_values=-1)
    pp = p + pad_q
    qt = jnp.swapaxes(q, 0, 1)                        # [H, P, D]
    block_tables = block_tables.astype(jnp.int32)

    def kv_map(h_, qi, j, bt):
        # -1 entries are clamped to a real block for the DMA; the body
        # masks them out entirely via `entry >= 0`
        return (jnp.clip(bt[j // m, j % m], 0, n_blocks - 1), h_ // g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, pp // block_q, b * m),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h_, qi, j, bt: (h_, qi, 0)),
            pl.BlockSpec((1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, t, d), kv_map),
            pl.BlockSpec((1, block_q), lambda h_, qi, j, bt: (0, qi)),
            pl.BlockSpec((1, block_q), lambda h_, qi, j, bt: (0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda h_, qi, j, bt: (h_, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window, block_tokens=t,
                          blocks_per_seq=m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, pp, d), q.dtype),
        interpret=interpret,
        name="paged_segment_attention",
    )(block_tables, qt, k_store, v_store,
      q_pos.astype(jnp.int32)[None], q_seg.astype(jnp.int32)[None])
    return jnp.swapaxes(out, 0, 1)[:p]
