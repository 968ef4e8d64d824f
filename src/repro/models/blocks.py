"""Per-layer blocks: init / apply / cache, dispatched on block *kind*.

Kinds (``ArchConfig.block_pattern`` entries):
  ``full``    causal full attention + FFN
  ``swa``     sliding-window attention (window = cfg.window)
  ``local``   same as swa (gemma3 local layers; ring KV cache)
  ``global``  full attention with the long-context rope theta (gemma3)
  ``bidir``   bidirectional attention (whisper encoder)
  ``rwkv6``   RWKV-6 time mix + channel mix (attention-free)
  ``rglru``   RG-LRU recurrent block + FFN (recurrentgemma)
A ``+moe`` suffix swaps the dense FFN for the MoE layer (e.g. ``full+moe``).

Every apply works in two modes:
  * full-seq (train / prefill): x [B,S,d]; optionally writes a decode cache.
  * step (decode): x [B,1,d] against the cache.
Caches are dict pytrees; attention caches hold (k, v, pos) with ring
semantics for windowed kinds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import A, shard
from . import layers, moe as moe_lib, rglru as rglru_lib, rwkv6 as rwkv6_lib
from .layers import apply_norm, norm_init

ATTN_KINDS = ("full", "swa", "local", "global", "bidir")
# kinds the chunked/bucketed prefill path can serve: attention via position
# masking, recurrent via the state-in/state-out scan kernels
CHUNKABLE_KINDS = ATTN_KINDS + ("rwkv6", "rglru")


def split_kind(kind: str) -> tuple[str, bool]:
    if kind.endswith("+moe"):
        return kind[:-4], True
    return kind, False


def block_init(key, cfg, kind: str) -> tuple[dict, dict]:
    base, is_moe = split_kind(kind)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params: dict = {}
    axes: dict = {}
    params["ln1"], axes["ln1"] = norm_init(cfg.norm, cfg.d_model, cfg.dtype)
    if base in ATTN_KINDS:
        params["attn"], axes["attn"] = layers.attention_init(k1, cfg)
    elif base == "rwkv6":
        params["tm_cm"], axes["tm_cm"] = rwkv6_lib.rwkv6_init(k1, cfg)
        params["ln2"], axes["ln2"] = norm_init(cfg.norm, cfg.d_model, cfg.dtype)
        return params, axes          # rwkv6 block has its own channel mix
    elif base == "rglru":
        params["rglru"], axes["rglru"] = rglru_lib.rglru_init(k1, cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    params["ln2"], axes["ln2"] = norm_init(cfg.norm, cfg.d_model, cfg.dtype)
    if is_moe:
        params["moe"], axes["moe"] = moe_lib.moe_init(k2, cfg)
    else:
        params["mlp"], axes["mlp"] = layers.mlp_init(
            k2, cfg.d_model, cfg.d_ff, cfg.mlp, cfg.dtype)
    return params, axes


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def cache_len_for(cfg, kind: str, seq_len: int, margin: int = 0) -> int:
    """Ring length for one layer's dense cache.  ``margin`` widens windowed
    rings past ``cfg.window``: speculative decode writes up to ``k`` draft
    positions past the pending token before the oldest in-window key is
    dead, so a ring must hold ``window + k`` entries or a rejected draft
    would overwrite a key the next tick still attends to."""
    base, _ = split_kind(kind)
    if base in ("swa", "local"):
        return min(cfg.window + margin, seq_len)
    return seq_len


def block_cache_init(cfg, kind: str, batch: int, seq_len: int,
                     ring_margin: int = 0):
    base, _ = split_kind(kind)
    if base in ATTN_KINDS:
        n = cache_len_for(cfg, kind, seq_len, margin=ring_margin)
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, n, cfg.num_kv_heads, hd), cfg.dtype),
            "v": jnp.zeros((batch, n, cfg.num_kv_heads, hd), cfg.dtype),
            "pos": jnp.full((batch, n), -1, jnp.int32),
        }
    if base == "rwkv6":
        return rwkv6_lib.init_state(cfg, batch)
    if base == "rglru":
        # exactly rglru_step's state structure: cache trees from init_cache
        # and from apply must match for per-slot merges to tree.map
        return rglru_lib.init_state(cfg, batch)
    raise ValueError(kind)


def paged_cache_init(cfg, kind: str, num_blocks: int, block_tokens: int):
    """Physical block store for one attention layer: ``[N, Kv, T, D]``
    (kernels/paged_attention ABI).  There is no ``pos`` plane — positions
    are implied by block-table order — and no per-slot batch axis: all
    sequences share the store through their tables."""
    base, _ = split_kind(kind)
    if base not in ATTN_KINDS:
        raise ValueError(f"paged KV requires attention blocks, got {kind!r}")
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((num_blocks, cfg.num_kv_heads, block_tokens, hd),
                       cfg.dtype),
        "v": jnp.zeros((num_blocks, cfg.num_kv_heads, block_tokens, hd),
                       cfg.dtype),
    }


def _paged_scatter(cache, k, v, pos, valid, block_tables, seg=None):
    """Write per-token K/V into the block store through the table.

    k, v: [B, C, Kv, D]; pos: [B, C] absolute logical positions; valid:
    [B, C] bool (False rows/tokens are dropped).  The routing is fully
    **per-token**: each token resolves its own table row — by default the
    batch row it sits in, or, when ``seg`` ([B, C] int32 slot ids, -1 =
    dead) is given, the slot it *belongs to* regardless of where it sits
    in the stream (the packed-prefill layout, where one [1, P] stream
    carries chunks from many requests).  Distinct logical positions map to
    distinct (block, offset) pairs, so the scatter never collides."""
    n, _, t, _ = cache["k"].shape
    b, m = block_tables.shape
    blk = jnp.clip(pos // t, 0, m - 1)
    if seg is None:
        entry = jnp.take_along_axis(block_tables, blk, axis=1)   # [B, C]
    else:
        entry = block_tables[jnp.clip(seg, 0, b - 1), blk]       # [*, C]
        valid = valid & (seg >= 0)
    phys = jnp.where(valid & (entry >= 0), entry, n)             # n => drop
    off = (pos % t).astype(jnp.int32)
    return {
        "k": cache["k"].at[phys, :, off].set(
            k.astype(cache["k"].dtype), mode="drop"),
        "v": cache["v"].at[phys, :, off].set(
            v.astype(cache["v"].dtype), mode="drop"),
    }


def paged_copy_blocks(cache, src, dst, block_axis: int = 0):
    """Copy whole physical blocks ``src[i] -> dst[i]`` within one layer's
    block store — the device side of ``KVLease.writable`` copy-on-write
    resolution: before a borrower writes into a block it shares with the
    prefix cache (or a forked lease), the engine re-homes the block and
    copies the shared bytes here.  ``src``/``dst`` are [P] int32 physical
    ids; the gather happens before the scatter, so a source is read at its
    pre-copy value even under donation.  Duplicate pairs are allowed (the
    engine pads the pair list to a power-of-two shape by repeating one
    pair — both writes carry identical bytes)."""
    def cp(a):
        vals = jnp.take(a, src, axis=block_axis)
        idx = (slice(None),) * block_axis + (dst,)
        return a.at[idx].set(vals)
    return {"k": cp(cache["k"]), "v": cp(cache["v"])}


def _paged_view(cache, block_tables):
    """Materialize the logical [B, M*T, Kv, D] K/V view plus its position
    plane (-1 behind unallocated table entries) — the XLA twin of the paged
    Pallas kernel's scalar-prefetch gather, used by chunked prefill where
    queries span many tokens.  Delegates to the kernel family's
    ``paged_gather`` so the block-table ABI has one decoder."""
    from repro.kernels.paged_attention import paged_gather
    k, v, k_pos = paged_gather(cache["k"], cache["v"], block_tables)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), k_pos


def _theta(cfg, base: str) -> float:
    if base == "global" and cfg.rope_theta_global:
        return cfg.rope_theta_global
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# apply: full sequence (train / prefill)
# ---------------------------------------------------------------------------


def block_apply_seq(cfg, kind: str, params: dict, x: jax.Array,
                    positions: jax.Array, cache=None):
    """x: [B,S,d]; positions: [S] absolute.  If ``cache`` is given (prefill),
    the computed K/V (or recurrent state) is written into it.
    Returns (x, cache, aux)."""
    base, is_moe = split_kind(kind)
    aux = jnp.zeros((), jnp.float32)

    if base == "rwkv6":
        p = params["tm_cm"]
        st = cache if cache is not None else rwkv6_lib.init_state(cfg, x.shape[0])
        h = apply_norm(cfg.norm, params["ln1"], x)
        y, S_new, tm_last = rwkv6_lib.time_mix_chunked(p, h, st["S"], st["tm_last"])
        x = x + y
        h2 = apply_norm(cfg.norm, params["ln2"], x)
        cm_out, cm_last = rwkv6_lib.channel_mix(p, h2, st["cm_last"])
        x = x + cm_out
        new_cache = {"S": S_new, "tm_last": tm_last, "cm_last": cm_last}
        return x, (new_cache if cache is not None else None), aux

    if base == "rglru":
        st = cache if cache is not None else rglru_lib.init_state(cfg, x.shape[0])
        h = apply_norm(cfg.norm, params["ln1"], x)
        y, st_new = rglru_lib.rglru_block(params["rglru"], h, st)
        x = x + y
    else:
        theta = _theta(cfg, base)
        h = apply_norm(cfg.norm, params["ln1"], x)
        q = layers.attn_project_q(params["attn"], h, positions=positions,
                                  theta=theta)
        k, v = layers.attn_project_kv(params["attn"], h, positions=positions,
                                      theta=theta)
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "seq", "kv_heads", None)
        window = cfg.window if base in ("swa", "local") else 0
        causal = base != "bidir"
        o = layers.attention(q, k, v, q_pos=positions, k_pos=positions,
                             causal=causal, window=window)
        x = x + layers.attn_output(params["attn"], o)
        if cache is not None:
            cache = _write_cache(cache, k, v, positions)
        st_new = None

    h2 = apply_norm(cfg.norm, params["ln2"], x)
    if is_moe:
        y, aux = moe_lib.moe_apply_ep(params["moe"], h2, cfg, return_aux=True)
    else:
        y = layers.mlp(params["mlp"], h2, cfg.mlp)
    x = x + y
    x = shard(x, "batch", "seq", "embed")
    new_cache = st_new if base == "rglru" else cache
    return x, new_cache, aux


def _write_cache(cache, k, v, positions):
    """Write full-seq K/V into a (possibly ring) cache."""
    n = cache["k"].shape[1]
    s = k.shape[1]
    if s >= n:  # keep the last n entries, ring-indexed
        k_tail, v_tail = k[:, -n:], v[:, -n:]
        pos_tail = positions[-n:]
        slots = (pos_tail % n).astype(jnp.int32)
        order = jnp.argsort(slots)
        return {
            "k": k_tail[:, order],
            "v": v_tail[:, order],
            "pos": jnp.broadcast_to(pos_tail[order], (k.shape[0], n)),
        }
    kc = cache["k"].at[:, :s].set(k)
    vc = cache["v"].at[:, :s].set(v)
    pc = cache["pos"].at[:, :s].set(jnp.broadcast_to(positions, (k.shape[0], s)))
    return {"k": kc, "v": vc, "pos": pc}


# ---------------------------------------------------------------------------
# apply: prompt chunk against a live cache (chunked / bucketed prefill)
# ---------------------------------------------------------------------------


def block_apply_chunk(cfg, kind: str, params: dict, x: jax.Array,
                      pos: jax.Array, valid: jax.Array, cache: dict,
                      block_tables: jax.Array | None = None):
    """x: [B,C,d] padded prompt chunk; pos: [B,C] absolute positions
    (row-wise contiguous, left-aligned); valid: [B,C] bool marks real
    tokens (False = pad or inactive slot); cache: attention KV cache or
    recurrent state.  With ``block_tables`` ([B,M] int32, attention kinds
    only) the cache is a paged block store: chunk K/V are scattered into
    physical blocks first, then queries attend to the table-gathered logical
    view (write-then-gather is exact because rows prefill front-to-back, so
    every position <= q_pos is written).

    Attention kinds: queries attend to (prior cache entries ++ in-chunk
    keys) under one softmax, so a chunk mid-prompt sees its full history
    exactly.  Only the last ``min(row_len, ring)`` valid K/V land in the
    cache (drop-mode scatter), which both respects ring semantics and keeps
    pad/inactive rows from ever touching cache state.

    Recurrent kinds (rwkv6 / rglru): scan state is threaded across the
    chunk boundary through the state-in/state-out kernel variants — pads are
    neutralized (decay 1, input 0) so per-row state advances over valid
    tokens only (the scan-state ABI, kernels/README.md).

    MoE FFNs route with ``valid``-aware capacity so pad tokens cannot steal
    expert slots from real ones (overflow semantics unchanged)."""
    base, is_moe = split_kind(kind)
    aux = jnp.zeros((), jnp.float32)

    if base in ("rwkv6", "rglru"):
        # a row whose chunk starts at position 0 is beginning its prompt in
        # a (possibly reused) slot: its scan state must restart from zero.
        # Attention caches mask the previous occupant's entries by position;
        # recurrent state has no positions, so the reset is explicit here.
        fresh = (pos[:, 0] == 0) & valid[:, 0]               # [B]

        def reset(st):
            return jax.tree.map(
                lambda a: jnp.where(
                    fresh.reshape((-1,) + (1,) * (a.ndim - 1)),
                    jnp.zeros_like(a), a), st)

        cache = reset(cache)

    if base == "rwkv6":
        p = params["tm_cm"]
        h = apply_norm(cfg.norm, params["ln1"], x)
        y, S_new, tm_last = rwkv6_lib.time_mix_chunk(
            p, h, cache["S"], cache["tm_last"], valid)
        x = x + y
        h2 = apply_norm(cfg.norm, params["ln2"], x)
        cm_out, cm_last = rwkv6_lib.channel_mix_chunk(
            p, h2, cache["cm_last"], valid)
        x = x + cm_out
        new_cache = {"S": S_new.astype(cache["S"].dtype),
                     "tm_last": tm_last.astype(cache["tm_last"].dtype),
                     "cm_last": cm_last.astype(cache["cm_last"].dtype)}
        return x, new_cache, aux

    if base == "rglru":
        h = apply_norm(cfg.norm, params["ln1"], x)
        y, new_cache = rglru_lib.rglru_chunk(params["rglru"], h, cache, valid)
        x = x + y
    elif base in ATTN_KINDS:
        theta = _theta(cfg, base)
        h = apply_norm(cfg.norm, params["ln1"], x)
        q = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wq"]),
                        pos, theta)
        k = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wk"]),
                        pos, theta)
        v = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wv"])

        window = cfg.window if base in ("swa", "local") else 0
        if block_tables is not None:
            new_cache = _paged_scatter(cache, k, v, pos, valid, block_tables)
            k_eff, v_eff, kpos_eff = _paged_view(new_cache, block_tables)
            o = layers.chunk_attention(q, k_eff, v_eff, k_pos=kpos_eff,
                                       q_pos=pos, window=window)
            x = x + layers.attn_output(params["attn"], o)
        else:
            kpos_chunk = jnp.where(valid, pos, -1).astype(jnp.int32)
            # cache entries at/after the chunk start are stale (a freed
            # slot's previous occupant); true history is strictly before it
            kpos_cache = jnp.where(cache["pos"] < pos[:, :1],
                                   cache["pos"], -1)
            k_eff = jnp.concatenate(
                [cache["k"], k.astype(cache["k"].dtype)], axis=1)
            v_eff = jnp.concatenate(
                [cache["v"], v.astype(cache["v"].dtype)], axis=1)
            kpos_eff = jnp.concatenate([kpos_cache, kpos_chunk], axis=1)
            o = layers.chunk_attention(q, k_eff, v_eff, k_pos=kpos_eff,
                                       q_pos=pos, window=window)
            x = x + layers.attn_output(params["attn"], o)

            # write-back: keep only each row's last min(len, n) valid
            # positions so ring slots are written at most once per call
            n = cache["k"].shape[1]
            row_len = valid.sum(axis=1).astype(jnp.int32)        # [B]
            last_pos = pos[:, 0] + row_len - 1
            keep = valid & (pos > (last_pos - n)[:, None])
            slots = jnp.where(keep, pos % n, n).astype(jnp.int32)  # n => drop
            bidx = jnp.arange(x.shape[0])[:, None]
            new_cache = {
                "k": cache["k"].at[bidx, slots].set(
                    k.astype(cache["k"].dtype), mode="drop"),
                "v": cache["v"].at[bidx, slots].set(
                    v.astype(cache["v"].dtype), mode="drop"),
                "pos": cache["pos"].at[bidx, slots].set(
                    pos.astype(jnp.int32), mode="drop"),
            }
    else:
        raise ValueError(f"chunked prefill cannot serve block kind {kind!r}")

    h2 = apply_norm(cfg.norm, params["ln2"], x)
    if is_moe:
        y = moe_lib.moe_apply_ep(params["moe"], h2, cfg, valid=valid)
    else:
        y = layers.mlp(params["mlp"], h2, cfg.mlp)
    x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# apply: token-packed ragged stream (packed prefill)
# ---------------------------------------------------------------------------


def block_apply_packed(cfg, kind: str, params: dict, x: jax.Array,
                       pos: jax.Array, slot_id: jax.Array, start: jax.Array,
                       seg_len: jax.Array, cache: dict,
                       block_tables: jax.Array | None = None):
    """One block over a token-packed ragged prefill stream.

    x: [1,P,d] — ONE flat stream holding contiguous chunks from up to B
    different requests (a new request's first chunk rides next to another
    request's later chunk); pos: [P] absolute position of each token in its
    own request; slot_id: [P] owning slot (-1 = dead pad, fully inert);
    start/seg_len: [B] per-slot chunk start and token count this call
    (the cu_seqlens twins: segment s spans stream indices
    ``[sum(seg_len[<s in stream order]), ...)``, but carrying them per-token
    keeps every mask O(1) to derive).  cache: the *batched* per-slot cache
    tree ([B, ...] leaves) or the paged block store.

    Attention kinds stay truly packed: queries attend through
    :func:`~repro.models.layers.segment_attention` against the flattened
    all-slot history view ++ in-stream keys, masked by segment id so no
    token ever sees another request; K/V write-back routes **per token** to
    its slot's dense ring row or paged block (``_paged_scatter`` with
    ``seg=slot_id``).

    Recurrent kinds (rwkv6/rglru) carry per-slot scan state with no
    position plane, so the stream is scattered to the per-slot left-aligned
    chunk layout, advanced through the existing scan-state ABI
    (:func:`block_apply_chunk`: pad neutralization, fresh-segment reset at
    position 0, MoE valid-aware capacity), and the outputs gathered back to
    their stream positions — segment-exact at B x P cost, which only the
    O(1)-state families pay."""
    base, is_moe = split_kind(kind)
    aux = jnp.zeros((), jnp.float32)
    p_len = x.shape[1]
    nslots = start.shape[0]
    valid = (slot_id >= 0)[None, :]                              # [1,P]

    if base in ("rwkv6", "rglru"):
        row = jnp.where(slot_id >= 0, slot_id, nslots)           # B => drop
        off = jnp.clip(pos - start[jnp.clip(slot_id, 0, nslots - 1)],
                       0, p_len - 1)
        xs = jnp.zeros((nslots, p_len, x.shape[2]), x.dtype)
        xs = xs.at[row, off].set(x[0], mode="drop")
        row_valid = (jnp.arange(p_len, dtype=jnp.int32)[None, :]
                     < seg_len[:, None])
        row_pos = start[:, None] + jnp.arange(p_len, dtype=jnp.int32)[None, :]
        y, new_cache, aux = block_apply_chunk(cfg, kind, params, xs, row_pos,
                                              row_valid, cache)
        xg = y[jnp.clip(slot_id, 0, nslots - 1), off][None]      # [1,P,d]
        return jnp.where(valid[..., None], xg, x), new_cache, aux

    if base not in ATTN_KINDS:
        raise ValueError(f"packed prefill cannot serve block kind {kind!r}")

    theta = _theta(cfg, base)
    h = apply_norm(cfg.norm, params["ln1"], x)
    pos2 = pos[None, :]                                          # [1,P]
    q = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wq"]),
                    pos2, theta)
    k = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wk"]),
                    pos2, theta)
    v = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wv"])
    window = cfg.window if base in ("swa", "local") else 0
    q_seg = slot_id[None, :]                                     # [1,P]

    if block_tables is not None:
        # write-then-attend (exact: segments advance front-to-back, so every
        # position <= q_pos of the same segment is live in the store); the
        # in-stream keys are therefore already inside the block store.  The
        # xla impl materializes the table-gathered view; the Pallas kernel
        # gathers blocks via scalar prefetch with the segment predicate
        # fused into the tile mask (key segment = table row).
        from repro.distributed.collectives import tp_paged_segment_attention
        new_cache = _paged_scatter(cache, k, v, pos2, valid, block_tables,
                                   seg=q_seg)
        o = tp_paged_segment_attention(
            q[0], new_cache["k"], new_cache["v"], block_tables, pos,
            slot_id, window=window)[None].astype(q.dtype)
        x = x + layers.attn_output(params["attn"], o)
    else:
        b, n = cache["k"].shape[0], cache["k"].shape[1]
        kvh, hd = cache["k"].shape[2], cache["k"].shape[3]
        # every slot's history, flattened to one key axis; entries at/after a
        # slot's chunk start are stale (a freed slot's previous occupant)
        kpos_cache = jnp.where(cache["pos"] < start[:, None],
                               cache["pos"], -1)
        k_eff = jnp.concatenate(
            [cache["k"].reshape(1, b * n, kvh, hd),
             k.astype(cache["k"].dtype)], axis=1)
        v_eff = jnp.concatenate(
            [cache["v"].reshape(1, b * n, kvh, hd),
             v.astype(cache["v"].dtype)], axis=1)
        kpos_eff = jnp.concatenate(
            [kpos_cache.reshape(1, b * n),
             jnp.where(valid, pos2, -1).astype(jnp.int32)], axis=1)
        kseg_eff = jnp.concatenate(
            [jnp.repeat(jnp.arange(b, dtype=jnp.int32), n)[None, :],
             q_seg], axis=1)
        o = layers.segment_attention(q, k_eff, v_eff, q_pos=pos2,
                                     k_pos=kpos_eff, q_seg=q_seg,
                                     k_seg=kseg_eff, window=window)
        x = x + layers.attn_output(params["attn"], o)

        # per-token write-back into each token's OWN slot row; ring
        # semantics per segment: keep only the last min(seg_len, n) valid
        # positions so a ring slot is written at most once per call
        last_pos = start + seg_len - 1                           # [B]
        keep = (slot_id >= 0) & (
            pos > (last_pos[jnp.clip(slot_id, 0, b - 1)] - n))
        rows = jnp.where(keep, slot_id, b)                       # b => drop
        cols = (pos % n).astype(jnp.int32)
        new_cache = {
            "k": cache["k"].at[rows, cols].set(
                k[0].astype(cache["k"].dtype), mode="drop"),
            "v": cache["v"].at[rows, cols].set(
                v[0].astype(cache["v"].dtype), mode="drop"),
            "pos": cache["pos"].at[rows, cols].set(
                pos.astype(jnp.int32), mode="drop"),
        }

    h2 = apply_norm(cfg.norm, params["ln2"], x)
    if is_moe:
        y = moe_lib.moe_apply_ep(params["moe"], h2, cfg, valid=valid)
    else:
        y = layers.mlp(params["mlp"], h2, cfg.mlp)
    x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# apply: packed stream with speculative (length-k) decode segments
# ---------------------------------------------------------------------------


def block_apply_spec(cfg, kind: str, params: dict, x: jax.Array,
                     pos: jax.Array, slot_id: jax.Array, start: jax.Array,
                     seg_len: jax.Array, spec_rows: jax.Array, l_max: int,
                     cache: dict, block_tables: jax.Array | None = None):
    """:func:`block_apply_packed` for a stream whose decode segments carry
    speculative drafts (length ``1 + d`` segments, ``spec_rows`` [B] bool
    marking them; ``l_max`` static max segment length).

    Attention kinds need nothing new: the segment predicate
    (same-segment & ``k_pos <= q_pos``) already verifies every draft
    offset exactly, and rejected-suffix K/V self-heals — stale entries
    are position-masked (dense) or overwritten before the gather (paged)
    on the next tick.  Delegates unchanged.

    Recurrent kinds (rwkv6/rglru) mutate state per token, so a rejected
    draft must be *rolled back*.  Spec rows therefore advance through
    ``l_max`` sequential single-column chunk calls, snapshotting the state
    after each offset; non-spec rows take the normal full-chunk path.
    Returns the cache as a pending pair ``{"spec_stack": [L,B,...],
    "spec_full": [B,...]}`` — the caller selects snapshot ``accept[b]``
    per spec row once acceptance is known (``transformer.step_spec``)."""
    base, _ = split_kind(kind)
    if base not in ("rwkv6", "rglru"):
        return block_apply_packed(cfg, kind, params, x, pos, slot_id, start,
                                  seg_len, cache, block_tables=block_tables)

    p_len = x.shape[1]
    nslots = start.shape[0]
    valid = (slot_id >= 0)[None, :]                              # [1,P]
    row = jnp.where(slot_id >= 0, slot_id, nslots)               # B => drop
    off = jnp.clip(pos - start[jnp.clip(slot_id, 0, nslots - 1)],
                   0, p_len - 1)
    xs = jnp.zeros((nslots, p_len, x.shape[2]), x.dtype)
    xs = xs.at[row, off].set(x[0], mode="drop")
    row_valid = (jnp.arange(p_len, dtype=jnp.int32)[None, :]
                 < seg_len[:, None])
    row_pos = start[:, None] + jnp.arange(p_len, dtype=jnp.int32)[None, :]

    # non-spec (prefill) rows: one full-chunk call, spec rows masked out so
    # their state never advances here (and the fresh-at-0 reset still fires
    # only for genuine prompt starts)
    y_full, cache_full, aux = block_apply_chunk(
        cfg, kind, params, xs, row_pos, row_valid & ~spec_rows[:, None],
        cache)

    # spec rows: offsets advance one column at a time from the pre-tick
    # state, snapshotting after each offset — pads are neutral in the chunk
    # kernels, so width-1 sequential calls compose exactly
    l_eff = min(int(l_max), p_len)
    st = cache
    snaps, cols = [], []
    for j in range(l_eff):
        col_valid = spec_rows[:, None] & row_valid[:, j:j + 1]
        yj, st, aux_j = block_apply_chunk(
            cfg, kind, params, xs[:, j:j + 1], row_pos[:, j:j + 1],
            col_valid, st)
        aux = aux + aux_j
        snaps.append(st)
        cols.append(yj)
    y_spec = jnp.concatenate(cols, axis=1)                       # [B,l_eff,d]
    stack = jax.tree.map(lambda *s: jnp.stack(s), *snaps)        # [L,B,...]

    y_sp = jnp.zeros_like(y_full).at[:, :l_eff].set(y_spec)
    y = jnp.where(spec_rows[:, None, None], y_sp, y_full)
    xg = y[jnp.clip(slot_id, 0, nslots - 1), off][None]          # [1,P,d]
    pending = {"spec_stack": stack, "spec_full": cache_full}
    return jnp.where(valid[..., None], xg, x), pending, aux


# ---------------------------------------------------------------------------
# apply: single decode step
# ---------------------------------------------------------------------------


def _keep_active(active, new_state, old_state):
    """Per-row select so inactive slots' recurrent state stays untouched."""
    def sel(new, old):
        a = active.reshape(active.shape + (1,) * (new.ndim - 1))
        return jnp.where(a, new.astype(old.dtype), old)
    return jax.tree.map(sel, new_state, old_state)


def block_apply_step(cfg, kind: str, params: dict, x: jax.Array,
                     pos: jax.Array, cache: dict, active=None,
                     block_tables: jax.Array | None = None):
    """x: [B,1,d]; pos: [B] absolute position of this token.  ``active``
    ([B] bool, optional) masks cache/state writes for slots that are not
    decoding this tick (free, or mid chunked-prefill).  ``block_tables``
    ([B,M] int32, attention kinds only) switches the KV cache to the paged
    block store: this token's K/V is scattered into its physical block and
    attention runs through the paged decode kernel."""
    base, is_moe = split_kind(kind)
    aux = jnp.zeros((), jnp.float32)

    if base == "rwkv6":
        p = params["tm_cm"]
        h = apply_norm(cfg.norm, params["ln1"], x)[:, 0]
        y, S_new, tm_last = rwkv6_lib.time_mix_step(p, h, cache["S"], cache["tm_last"])
        x = x + y[:, None, :]
        h2 = apply_norm(cfg.norm, params["ln2"], x)[:, 0]
        cm_out, cm_last = rwkv6_lib.channel_mix(p, h2, cache["cm_last"])
        x = x + cm_out[:, None, :]
        new_cache = {"S": S_new, "tm_last": tm_last, "cm_last": cm_last}
        if active is not None:
            new_cache = _keep_active(active, new_cache, cache)
        return x, new_cache, aux

    if base == "rglru":
        h = apply_norm(cfg.norm, params["ln1"], x)[:, 0]
        y, st_new = rglru_lib.rglru_step(params["rglru"], h, cache)
        x = x + y[:, None, :]
        if active is not None:
            st_new = _keep_active(active, st_new, cache)
        new_cache = st_new
    else:
        theta = _theta(cfg, base)
        h = apply_norm(cfg.norm, params["ln1"], x)
        pos2d = pos[:, None]                                  # [B,1]
        q = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wq"]),
                        pos2d, theta)
        k_t = layers.rope(jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wk"]),
                          pos2d, theta)
        v_t = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wv"])
        window = cfg.window if base in ("swa", "local") else 0
        if block_tables is not None:
            ok = jnp.ones(pos.shape, bool) if active is None else active
            new_cache = _paged_scatter(cache, k_t, v_t, pos[:, None],
                                       ok[:, None], block_tables)
            from repro.distributed.collectives import (
                tp_paged_decode_attention)
            o = tp_paged_decode_attention(q[:, 0], new_cache["k"],
                                          new_cache["v"], block_tables, pos,
                                          window=window)
            x = x + layers.attn_output(params["attn"], o[:, None])
        else:
            n = cache["k"].shape[1]
            slot = (pos % n).astype(jnp.int32)                # ring or direct
            if active is not None:
                slot = jnp.where(active, slot, n)             # n => dropped
            bidx = jnp.arange(x.shape[0])
            kc = cache["k"].at[bidx, slot].set(k_t[:, 0], mode="drop")
            vc = cache["v"].at[bidx, slot].set(v_t[:, 0], mode="drop")
            pc = cache["pos"].at[bidx, slot].set(pos.astype(jnp.int32),
                                                 mode="drop")
            o = layers.decode_attention(q, kc, vc, k_pos=pc, q_pos=pos,
                                        window=window)
            x = x + layers.attn_output(params["attn"], o)
            new_cache = {"k": kc, "v": vc, "pos": pc}

    h2 = apply_norm(cfg.norm, params["ln2"], x)
    if is_moe:
        y = moe_lib.moe_apply_ep_serve(
            params["moe"], h2, cfg,
            valid=None if active is None else active[:, None])
    else:
        y = layers.mlp(params["mlp"], h2, cfg.mlp)
    x = x + y
    return x, new_cache, aux
