"""Pallas TPU fused segment-attention kernels: one packed query stream
against segment-tagged keys, without ever materializing the ``[H, P, N]``
score matrix.

Two entry points share one online-softmax body structure:

  * :func:`segment_attention` — keys are a flat axis carrying per-key
    ``(k_pos, k_seg)`` tags: the dense packed path's flattened all-slot ring
    view ++ in-stream keys.  Grid = (heads, q_tiles, k_tiles), k innermost /
    sequential; the (m, l, acc) state lives in VMEM scratch per q tile.
    Every q tile walks every k tile: a tile the predicate fully masks skips
    its matmul, but its K/V tile is still copied in.
  * :func:`paged_segment_attention` — keys live in the paged block store
    and are gathered through per-slot block tables.  Grid = (heads,
    q_tiles).  Outside the kernel, :func:`live_block_ranges` reckons for
    each query tile and slot the first and last table index its lanes can
    see (causal bound, window bound); the table and those ranges are
    **scalar-prefetch** operands.  Each grid step walks only its ranges,
    ``C`` blocks at a time (:func:`kv_chunk_blocks`): manual async copies
    from the HBM store into a double-buffered VMEM chunk, the next chunk's
    copies issued before the current one is computed.  Only a range's last
    chunk copies blocks past it (clamped, and masked in the body); blocks
    outside every range are never fetched, so key work and K/V copies
    follow the live predicate.  Key positions are implied by table order, key
    segments by table row, so no ``[B, M*T]`` logical view is ever
    materialized.

The same-segment / written / causal / window predicate is fused into the
tile mask (the packed-segment ABI of ``models.layers.segment_attention``).
GQA is handled by gridding over *query* heads and mapping each to its KV
head (``h // group``), so no K/V repetition happens.  Fully-masked queries
(dead pad lanes, ``q_seg < 0``) finish with ``l == 0`` and emit exact
zeros — bit-identical to the ref oracle on every lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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




def q_tiling(p: int, block_q: int = DEFAULT_BLOCK_Q) -> tuple[int, int]:
    """The query tile of a ``p``-lane stream and the dead lanes that pad it
    to whole tiles: ``(block_q, pad)``."""
    block_q = min(block_q, p)
    return block_q, (-p) % block_q


def kv_chunk_blocks(block_tokens: int, head_dim: int, max_blocks: int) -> int:
    """Blocks one step of the paged walk fetches: 256 keys at head dims up
    to 128, 128 above, never more blocks than a table row holds."""
    keys = 256 if head_dim <= 128 else 128
    return max(1, min(keys // block_tokens, max_blocks))


def live_block_ranges(q_pos, q_seg, *, num_slots: int, max_blocks: int,
                      block_tokens: int, window: int = 0,
                      block_q: int = DEFAULT_BLOCK_Q):
    """For each query tile and slot, the first and last block-table index
    that the tile's lanes of that slot can see: int32 ``[q_tiles,
    num_slots, 2]``.  The last is ``max(q_pos) // T`` over the tile's lanes
    of the slot; the first is ``max(0, min(q_pos) - window + 1) // T`` under
    a window, else 0.  A slot with no lane in the tile gets ``last <
    first``.  Takes numpy arrays (the engine's host-side count) as well as
    jax ones (the kernel's scalar-prefetch operand)."""
    xp = np if isinstance(q_pos, np.ndarray) else jnp
    bq, pad = q_tiling(q_pos.shape[0], block_q)
    pos = xp.pad(q_pos.astype(np.int32), (0, pad)).reshape(-1, bq, 1)
    seg = xp.pad(q_seg.astype(np.int32), (0, pad), constant_values=-1)
    mine = seg.reshape(-1, bq, 1) == xp.arange(num_slots, dtype=np.int32)
    last = xp.minimum(xp.max(xp.where(mine, pos, -1), axis=1) // block_tokens,
                      max_blocks - 1)
    if window > 0:
        low = xp.min(xp.where(mine, pos, np.iinfo(np.int32).max), axis=1)
        first = xp.maximum(low - window + 1, 0) // block_tokens
    else:
        first = xp.zeros_like(last)
    return xp.stack([first, last], axis=-1).astype(np.int32)


def live_blocks(ranges: np.ndarray) -> int:
    """Blocks a walk of :func:`live_block_ranges` visits, per query head."""
    return int(np.maximum(ranges[..., 1] - ranges[..., 0] + 1, 0).sum())


def _paged_kernel(bt_ref, rng_ref, q_ref, qpos_ref, qseg_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                  window: int, chunk: int, group: int):
    h, qi = pl.program_id(0), pl.program_id(1)
    n_slots, m_blocks = bt_ref.shape
    t = k_hbm.shape[2]
    kvh = h // group

    def first(s):
        return rng_ref[(qi * n_slots + s) * 2]

    def last(s):
        return rng_ref[(qi * n_slots + s) * 2 + 1]

    def next_slot(after):
        """The first slot past ``after`` with a live range; ``n_slots`` if
        none is left."""
        nxt = jnp.int32(n_slots)
        for s in reversed(range(n_slots)):
            nxt = jnp.where((s > after) & (first(s) <= last(s)), s, nxt)
        return nxt

    def blocks(s, j0):
        """(physical id to copy, live) of the chunk's blocks.  Every chunk
        copies ``chunk`` blocks: a -1 entry, or a block past the slot's
        range, is clamped to a real block for the copy (issuing without a
        branch costs less than the copy it would skip) and masked in the
        body."""
        hi = last(s)
        out = []
        for i in range(chunk):
            j = j0 + i
            entry = bt_ref[s, jnp.minimum(j, m_blocks - 1)]
            out.append((jnp.maximum(entry, 0), (j <= hi) & (entry >= 0)))
        return out

    def copies(s, j0, buf, method):
        for i, (entry, _) in enumerate(blocks(s, j0)):
            rows = pl.ds(i * t, t)
            for kind, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[entry, kvh], vmem.at[buf, rows],
                    sems.at[kind, buf]), method)()

    def attend(s, j0, buf):
        col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk * t), 1)
        live = jnp.zeros((1, chunk * t), jnp.int32)
        for i, (_, ok) in enumerate(blocks(s, j0)):
            live = jnp.where((col >= i * t) & (col < (i + 1) * t),
                             ok.astype(jnp.int32), live)
        kp = j0 * t + col                             # [1, C*T]
        qp = qpos_ref[0][:, None]                     # [bq, 1]
        qs = qseg_ref[0][:, None]
        valid = (live > 0) & (qs == s) & (kp <= qp)
        if window > 0:
            valid &= (qp - kp) < window

        @pl.when(valid.any())
        def _update():
            q = q_ref[0].astype(jnp.float32)          # [bq, d]
            k = k_buf[buf].astype(jnp.float32)        # [C*T, d]
            v = v_buf[buf].astype(jnp.float32)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc *= q.shape[-1] ** -0.5                 # [bq, C*T]
            _online_update(sc, valid, v, m_scr, l_scr, acc_scr)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(carry):
        s, j0, buf = carry
        more = j0 + chunk <= last(s)
        ns = jnp.where(more, s, next_slot(s))
        nj = jnp.where(more, j0 + chunk, first(jnp.minimum(ns, n_slots - 1)))

        @pl.when(ns < n_slots)
        def _prefetch():
            copies(ns, nj, 1 - buf, "start")

        copies(s, j0, buf, "wait")
        attend(s, j0, buf)
        return ns, nj, 1 - buf

    s0 = next_slot(-1)
    j00 = first(jnp.minimum(s0, n_slots - 1))

    @pl.when(s0 < n_slots)
    def _first():
        copies(s0, j00, 0, "start")

    jax.lax.while_loop(lambda c: c[0] < n_slots, step,
                       (s0, j00, jnp.int32(0)))
    _finish(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "block_q",
                                             "interpret"))
def paged_segment_attention(q: jax.Array, k_store: jax.Array,
                            v_store: jax.Array, block_tables: jax.Array,
                            q_pos: jax.Array, q_seg: jax.Array, *,
                            window: int = 0, block_q: int = DEFAULT_BLOCK_Q,
                            interpret: bool = False) -> jax.Array:
    """q: [P, H, D]; k_store/v_store: [N, Kv, T, D]; block_tables: [B, M]
    int32 (-1 = unallocated, clamped for the copy and masked in the body);
    q_pos/q_seg: [P] (segment id == block-table row) -> [P, H, D]."""
    p, h, d = q.shape
    _, kvh, t, _ = k_store.shape
    b, m = block_tables.shape
    q_pos = q_pos.astype(jnp.int32)
    q_seg = q_seg.astype(jnp.int32)
    ranges = live_block_ranges(q_pos, q_seg, num_slots=b, max_blocks=m,
                               block_tokens=t, window=window, block_q=block_q)
    block_q, pad_q = q_tiling(p, block_q)
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q))
        q_seg = jnp.pad(q_seg, (0, pad_q), constant_values=-1)
    chunk = kv_chunk_blocks(t, d, m)
    qt = jnp.swapaxes(q, 0, 1)                        # [H, P, D]

    def lanes(h_, qi, bt, rng):
        return 0, qi

    def tile(h_, qi, bt, rng):
        return h_, qi, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, (p + pad_q) // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), tile),
            pl.BlockSpec((1, block_q), lanes),
            pl.BlockSpec((1, block_q), lanes),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), tile),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * t, d), k_store.dtype),
            pltpu.VMEM((2, chunk * t, d), v_store.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window, chunk=chunk,
                          group=h // kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, p + pad_q, d), q.dtype),
        interpret=interpret,
        name="paged_segment_attention",
    )(block_tables.astype(jnp.int32), ranges.reshape(-1), qt, q_pos[None],
      q_seg[None], k_store, v_store)
    return jnp.swapaxes(out, 0, 1)[:p]
