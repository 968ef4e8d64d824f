"""The model: decoder-only LM (all families) + encoder-decoder (whisper).

Layer stacking uses a **group scan**: the repeating ``block_pattern`` (e.g.
gemma3's 5 local + 1 global, recurrentgemma's rglru/rglru/attn) becomes one
scan body with per-slot static code; parameters are stacked across groups so
the HLO is O(pattern), not O(num_layers).  ``first_k_dense`` prefix layers
and the pattern remainder are unrolled explicitly.

Public entry points (all pure):
    init(cfg, key)                      -> (params, axes)
    init_params(cfg, key)               -> params  [one jitted program]
    forward(cfg, params, batch)         -> logits | hidden
    loss_fn(cfg, params, batch)         -> (loss, aux)     [chunked CE]
    prefill(cfg, params, batch, cache_len) -> (last_logits, caches)
    prefill_chunk(cfg, params, caches, tokens, start, lengths)
                                        -> (last_logits, caches)  [in-place]
    step_packed(cfg, params, caches, tokens, slot_id, pos, start, seg_len)
                                        -> (last_logits, caches)  [in-place;
                                        one ragged stream of prefill chunks
                                        + length-1 decode segments]
    step_spec(cfg, params, caches, tokens, slot_id, pos, start, seg_len,
              spec_rows, spec_idx, draft_len)
                                        -> (accept, toks, caches) [packed
                                        stream whose decode segments carry
                                        length-(1+d) speculative drafts;
                                        greedy acceptance computed in-graph]
    decode_step(cfg, params, caches, token, pos) -> (logits, caches)
    init_cache(cfg, batch, cache_len)   -> caches
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import A, Axes, shard
from . import blocks as B
from .layers import _dense_init, apply_norm, norm_init, attention
from . import layers

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# structure helpers
# ---------------------------------------------------------------------------


def _plan(cfg):
    """(prefix_kinds, pattern, n_groups, remainder_kinds) for the decoder."""
    pattern = tuple(cfg.block_pattern)
    n_prefix = cfg.first_k_dense
    n_rest = cfg.num_layers - n_prefix
    n_groups, rem = divmod(n_rest, len(pattern))
    prefix = tuple(_strip_moe(pattern[i % len(pattern)]) for i in range(n_prefix))
    return prefix, pattern, n_groups, pattern[:rem]


def _strip_moe(kind: str) -> str:
    base, _ = B.split_kind(kind)
    return base


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(cfg, key) -> tuple[dict, dict]:
    prefix, pattern, n_groups, rem = _plan(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {}
    axes: dict = {}

    params["embed"] = _dense_init(keys[0], (cfg.vocab_size, cfg.d_model), cfg.dtype)
    axes["embed"] = A("vocab", "embed")
    if not cfg.tie_embeddings:
        params["head"] = _dense_init(keys[1], (cfg.d_model, cfg.vocab_size), cfg.dtype)
        axes["head"] = A("embed", "vocab")
    params["ln_f"], axes["ln_f"] = norm_init(cfg.norm, cfg.d_model, cfg.dtype)

    if cfg.frontend == "vision":
        k1, k2 = jax.random.split(keys[2])
        params["connector"] = {
            "w1": _dense_init(k1, (cfg.frontend_dim, cfg.d_model), cfg.dtype),
            "w2": _dense_init(k2, (cfg.d_model, cfg.d_model), cfg.dtype),
        }
        axes["connector"] = {"w1": A(None, "embed"), "w2": A("embed", "embed")}
    if cfg.encoder_decoder:
        # learned absolute positions (whisper)
        max_pos = 65536
        params["pos_emb"] = jnp.zeros((max_pos, cfg.d_model), cfg.dtype)
        axes["pos_emb"] = A(None, "embed")

    def stack_axes(ax_tree):
        # stacked params gain a leading layer/group dim: unsharded
        return jax.tree.map(
            lambda ax: A(None, *ax.names), ax_tree,
            is_leaf=lambda x: isinstance(x, Axes))

    def stack_init(kinds, key, n_copies=1, *, stack=False):
        keys = []                                   # [n_copies][len(kinds)]
        for _ in range(n_copies):
            kp, key = jax.random.split(key)
            row = []
            for _ in kinds:
                kj, kp = jax.random.split(kp)
                row.append(kj)
            keys.append(row)
        axs = []

        def group_init(row):
            out = [B.block_init(kj, cfg, kind) for kj, kind in zip(row, kinds)]
            axs[:] = [a for _, a in out]
            return [p for p, _ in out]

        if not stack:
            return group_init(keys[0]), axs
        # each copy is written straight into the stacked arrays, so one
        # group's worth of temporaries is live at a time, never two copies
        # of the whole stack
        _, stacked = jax.lax.scan(
            lambda _, row: (None, group_init(list(row))), None,
            jnp.stack([jnp.stack(row) for row in keys]))
        return stacked, axs

    if prefix:
        params["prefix"], axes["prefix"] = stack_init(prefix, keys[3])
    if n_groups:
        params["groups"], ga = stack_init(pattern, keys[4], n_groups,
                                          stack=True)
        axes["groups"] = stack_axes(ga)
    if rem:
        params["rem"], axes["rem"] = stack_init(rem, keys[5])

    if cfg.encoder_decoder:
        enc_p, enc_a = [], None
        kp = keys[6]
        for _ in range(cfg.enc_layers):
            kj, kp = jax.random.split(kp)
            p, a = B.block_init(kj, cfg, "bidir")
            enc_p.append(p)
            enc_a = a
        params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc_p)
        axes["encoder"] = stack_axes(enc_a)
        params["ln_enc"], axes["ln_enc"] = norm_init(cfg.norm, cfg.d_model, cfg.dtype)
        # cross attention per decoder layer (stacked over ALL layers)
        xp, xa = [], None
        for _ in range(cfg.num_layers):
            kj, kp = jax.random.split(kp)
            p, a = layers.attention_init(kj, cfg)
            ln, lna = norm_init(cfg.norm, cfg.d_model, cfg.dtype)
            xp.append({"attn": p, "ln": ln})
            xa = {"attn": a, "ln": lna}
        params["cross"] = jax.tree.map(lambda *xs: jnp.stack(xs), *xp)
        axes["cross"] = stack_axes(xa)
    return params, axes


def init_params(cfg, key, *, sharding=None) -> dict:
    """:func:`init`'s params, built on device by one jitted program.

    ``sharding`` (optional) places every leaf as it is built: replicated
    over a serving mesh, each device makes its own copy, and no device
    ever holds a second one in transit."""
    return jax.jit(lambda k: init(cfg, k)[0], out_shardings=sharding)(key)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens]
    return shard(x, "batch", "seq", "embed")


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    return shard(logits, "batch", "seq", "vocab")


def _inputs_embeds(cfg, params, batch):
    """Token embeddings, with modality prefixes where configured.
    Returns (x [B,S,d], positions [S])."""
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens)
    if cfg.frontend == "vision":
        p = batch["patches"]                       # [B,P,frontend_dim]
        c = params["connector"]
        pe = jax.nn.gelu(p.astype(cfg.dtype) @ c["w1"]) @ c["w2"]
        x = jnp.concatenate([pe, x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    if "pos_emb" in params and not cfg.encoder_decoder:
        x = x + params["pos_emb"][positions]
    return x, positions


# ---------------------------------------------------------------------------
# decoder trunk (full-seq)
# ---------------------------------------------------------------------------


def _run_blocks_seq(cfg, params, x, positions, *, enc_out=None, caches=None,
                    remat: str = "none"):
    """Runs prefix -> scanned groups -> remainder.  caches=None for training;
    otherwise a cache pytree from init_cache to be filled (prefill)."""
    prefix, pattern, n_groups, rem = _plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    layer_idx = 0

    def maybe_cross(x, li):
        if enc_out is None:
            return x
        cp = jax.tree.map(lambda t: t[li], params["cross"])
        h = apply_norm(cfg.norm, cp["ln"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, cp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wv"])
        kp = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
        qp = jnp.arange(x.shape[1], dtype=jnp.int32)
        o = attention(q, k, v, q_pos=qp, k_pos=kp, causal=False, window=0)
        return x + layers.attn_output(cp["attn"], o)

    # -- prefix (unrolled)
    for j, kind in enumerate(prefix):
        c = None if caches is None else caches["prefix"][j]
        x, c, aux = B.block_apply_seq(cfg, kind, params["prefix"][j], x,
                                      positions, cache=c)
        x = maybe_cross(x, layer_idx)
        if caches is not None:
            caches["prefix"][j] = c
        aux_total += aux
        layer_idx += 1

    # -- scanned groups
    if n_groups:
        group_params = params["groups"]
        has_cross = enc_out is not None

        def group_body(carry, xs):
            x, aux_in, li = carry
            gp, gc = xs
            new_caches = []
            for j, kind in enumerate(pattern):
                cj = None if gc is None else gc[j]
                x, cj, aux = B.block_apply_seq(cfg, kind, gp[j], x,
                                               positions, cache=cj)
                if has_cross:
                    # cross-attn params indexed dynamically per layer
                    cp = jax.tree.map(
                        lambda t: jax.lax.dynamic_index_in_dim(
                            t, li + j, 0, keepdims=False), params["cross"])
                    h = apply_norm(cfg.norm, cp["ln"], x)
                    q = jnp.einsum("bsd,dhk->bshk", h, cp["attn"]["wq"])
                    k = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wk"])
                    v = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wv"])
                    kp = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
                    qp = jnp.arange(x.shape[1], dtype=jnp.int32)
                    o = attention(q, k, v, q_pos=qp, k_pos=kp, causal=False,
                                  window=0)
                    x = x + layers.attn_output(cp["attn"], o)
                new_caches.append(cj)
                aux_in = aux_in + aux
            ys = new_caches if gc is not None else None
            return (x, aux_in, li + len(pattern)), ys

        body = group_body
        if remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else None)
            body = jax.checkpoint(group_body, policy=policy,
                                  prevent_cse=False)

        gcaches = None if caches is None else caches["groups"]
        (x, aux_total, layer_idx), group_caches_out = jax.lax.scan(
            body, (x, aux_total, jnp.asarray(layer_idx, jnp.int32)),
            (group_params, gcaches))
        if caches is not None:
            caches["groups"] = group_caches_out

    # -- remainder (unrolled)
    for j, kind in enumerate(rem):
        c = None if caches is None else caches["rem"][j]
        x, c, aux = B.block_apply_seq(cfg, kind, params["rem"][j], x,
                                      positions, cache=c)
        x = maybe_cross(x, layer_idx)
        if caches is not None:
            caches["rem"][j] = c
        aux_total += aux
        layer_idx += 1

    return x, caches, aux_total


def _run_encoder(cfg, params, frames):
    """whisper encoder over precomputed frame embeddings [B,Se,d]."""
    x = frames.astype(cfg.dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    if "pos_emb" in params:
        x = x + params["pos_emb"][positions]

    def body(x, lp):
        x, _, _ = B.block_apply_seq(cfg, "bidir", lp, x, positions)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return apply_norm(cfg.norm, params["ln_enc"], x)


# ---------------------------------------------------------------------------
# public: forward / loss
# ---------------------------------------------------------------------------


def forward(cfg, params, batch, *, remat: str = "none"):
    enc_out = None
    if cfg.encoder_decoder:
        enc_out = _run_encoder(cfg, params, batch["frames"])
    x, positions = _inputs_embeds(cfg, params, batch)
    if "pos_emb" in params and cfg.encoder_decoder:
        x = x + params["pos_emb"][positions]
    x, _, aux = _run_blocks_seq(cfg, params, x, positions, enc_out=enc_out,
                                remat=remat)
    x = apply_norm(cfg.norm, params["ln_f"], x)
    return x, aux


def loss_fn(cfg, params, batch, *, remat: str = "dots",
            aux_weight: float = 0.01):
    """Chunked cross-entropy: the [B,S,V] logits tensor never materializes
    (decisive for 262k-vocab gemma3 at 1M tokens)."""
    x, aux = forward(cfg, params, batch, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision":               # prefix positions carry no loss
        x = x[:, -labels.shape[1]:]
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    b, s, d = x.shape
    chunk = min(LOSS_CHUNK, s)
    while s % chunk:
        chunk -= 1
    n = s // chunk
    xc = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk_loss(x_i, l_i):
        logits = jnp.einsum("bsd,dv->bsv", x_i, head).astype(jnp.float32)
        logits = shard(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l_i[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def body(acc, xs):
        x_i, l_i = xs
        return acc + chunk_loss(x_i, l_i), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    loss = total / (b * s)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# public: serving
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, cache_len: int, ring_margin: int = 0):
    """``ring_margin`` widens windowed (swa/local) rings past ``cfg.window``
    — required when speculative drafts write up to ``k`` rejected positions
    past the pending token (see :func:`blocks.cache_len_for`)."""
    prefix, pattern, n_groups, rem = _plan(cfg)
    caches = {}
    if prefix:
        caches["prefix"] = [B.block_cache_init(cfg, k, batch, cache_len,
                                               ring_margin=ring_margin)
                            for k in prefix]
    if n_groups:
        group = [B.block_cache_init(cfg, k, batch, cache_len,
                                    ring_margin=ring_margin) for k in pattern]
        caches["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(), group)
    if rem:
        caches["rem"] = [B.block_cache_init(cfg, k, batch, cache_len,
                                            ring_margin=ring_margin)
                         for k in rem]
    if cfg.encoder_decoder:
        caches["enc_out"] = jnp.zeros((batch, cfg.enc_seq, cfg.d_model), cfg.dtype)
    return caches


def prefill(cfg, params, batch, *, cache_len: int):
    tokens = batch["tokens"]
    enc_out = None
    if cfg.encoder_decoder:
        enc_out = _run_encoder(cfg, params, batch["frames"])
    x, positions = _inputs_embeds(cfg, params, batch)
    if "pos_emb" in params and cfg.encoder_decoder:
        x = x + params["pos_emb"][positions]
    caches = init_cache(cfg, tokens.shape[0], cache_len)
    if cfg.encoder_decoder:
        caches["enc_out"] = enc_out
    x, caches, _ = _run_blocks_seq(cfg, params, x, positions, enc_out=enc_out,
                                   caches=caches)
    x = apply_norm(cfg.norm, params["ln_f"], x)
    logits = _logits(cfg, params, x[:, -1:, :])[:, 0]
    return logits, caches


def _all_kinds(cfg) -> set:
    return set(cfg.block_pattern) | {k for k in (_plan(cfg)[0] or ())}


def supports_chunked_prefill(cfg) -> bool:
    """Chunked/bucketed (padded) prefill needs every block to either be
    position-maskable (attention kinds) or to thread scan state across chunk
    boundaries through the state-in/state-out kernel variants (rwkv6/rglru,
    with pads neutralized); MoE routing is pad-aware, so MoE archs qualify
    too.  Only the vision/encoder-decoder frontends — whose unpadded
    modality prefixes have no chunk representation — keep the exact one-shot
    path, and requesting chunked prefill for them raises."""
    if cfg.encoder_decoder or cfg.frontend == "vision":
        return False
    return all(B.split_kind(k)[0] in B.CHUNKABLE_KINDS
               for k in _all_kinds(cfg))


def supports_paged_kv(cfg) -> bool:
    """Paged KV (block-table cache + paged decode kernel) needs every block
    to be a dense-attention kind (MoE FFNs are fine — only the attention
    K/V is paged) and prefill to go through the chunked path (the one-shot
    legacy prefill builds a dense per-slot cache with no paged equivalent).
    Recurrent blocks carry O(1) state — nothing to page — so rwkv6/rglru
    archs serve chunked prefill from the dense per-slot cache instead."""
    if not supports_chunked_prefill(cfg):
        return False
    return all(B.split_kind(k)[0] in B.ATTN_KINDS for k in _all_kinds(cfg))


def init_paged_cache(cfg, num_blocks: int, block_tokens: int):
    """Per-layer physical block stores ``[num_blocks, Kv, T, D]`` replacing
    the dense per-slot cache (structure mirrors :func:`init_cache`)."""
    if not supports_paged_kv(cfg):
        raise ValueError(f"{cfg.name}: block pattern {cfg.block_pattern} "
                         "does not support paged KV")
    prefix, pattern, n_groups, rem = _plan(cfg)
    caches = {}
    if prefix:
        caches["prefix"] = [B.paged_cache_init(cfg, k, num_blocks, block_tokens)
                            for k in prefix]
    if n_groups:
        group = [B.paged_cache_init(cfg, k, num_blocks, block_tokens)
                 for k in pattern]
        caches["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(), group)
    if rem:
        caches["rem"] = [B.paged_cache_init(cfg, k, num_blocks, block_tokens)
                         for k in rem]
    return caches


def map_paged_caches(caches, fn):
    """Apply ``fn(array, block_axis)`` to every store plane of a paged cache
    tree (block axis 0 for prefix/rem layers, 1 for the group-stacked ones).
    Used by the engine to physically resize the block store when
    ``serve.kv_block_budget`` moves."""
    out = dict(caches)
    if "prefix" in caches:
        out["prefix"] = [{n: fn(a, 0) for n, a in c.items()}
                         for c in caches["prefix"]]
    if "groups" in caches:
        out["groups"] = [jax.tree.map(lambda a: fn(a, 1), c)
                         for c in caches["groups"]]
    if "rem" in caches:
        out["rem"] = [{n: fn(a, 0) for n, a in c.items()}
                      for c in caches["rem"]]
    return out


def copy_paged_blocks(caches, src, dst):
    """Block-level copy-on-write across every layer of a paged cache tree:
    physical blocks ``src[i] -> dst[i]`` in each store plane (the engine
    jits this with cache donation and applies it before a lease's first
    write into a shared block — see ``KVLease.writable``)."""
    out = dict(caches)
    if "prefix" in caches:
        out["prefix"] = [B.paged_copy_blocks(c, src, dst)
                         for c in caches["prefix"]]
    if "groups" in caches:
        out["groups"] = [B.paged_copy_blocks(c, src, dst, block_axis=1)
                         for c in caches["groups"]]
    if "rem" in caches:
        out["rem"] = [B.paged_copy_blocks(c, src, dst)
                      for c in caches["rem"]]
    return out


def prefill_chunk(cfg, params, caches, tokens, start, lengths,
                  block_tables=None):
    """Advance prefill by one padded chunk per batch row, in place.

    tokens: [B,C] int32 (row-wise left-aligned, zero-padded); start: [B]
    absolute position of each row's first chunk token; lengths: [B] valid
    tokens this chunk (0 = inactive row: no cache/state writes, garbage
    logits).  ``block_tables`` ([B,M] int32, optional) switches the
    attention caches to paged block stores.  Returns (next-token logits
    [B,V] at each row's last valid position, caches).  Attention chunks
    attend to prior chunks through the cache; recurrent blocks thread their
    scan state across the boundary (state-in/state-out kernels, pads
    neutralized); MoE routing is ``valid``-aware — so calling this
    repeatedly over a long prompt is exact chunked prefill for every
    supported family."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"{cfg.name}: block pattern {cfg.block_pattern} "
                         "does not support chunked prefill")
    prefix, pattern, n_groups, rem = _plan(cfg)
    b, c = tokens.shape
    pos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]   # [B,C]
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < lengths[:, None]
    x = params["embed"][tokens]

    for j, kind in enumerate(prefix):
        x, caches["prefix"][j], _ = B.block_apply_chunk(
            cfg, kind, params["prefix"][j], x, pos, valid,
            caches["prefix"][j], block_tables=block_tables)

    if n_groups:
        def group_body(x, xs):
            gp, gc = xs
            new_c = []
            for j, kind in enumerate(pattern):
                x, cj, _ = B.block_apply_chunk(cfg, kind, gp[j], x, pos,
                                               valid, gc[j],
                                               block_tables=block_tables)
                new_c.append(cj)
            return x, new_c

        x, new_groups = jax.lax.scan(
            group_body, x, (params["groups"], caches["groups"]))
        caches["groups"] = new_groups

    for j, kind in enumerate(rem):
        x, caches["rem"][j], _ = B.block_apply_chunk(
            cfg, kind, params["rem"][j], x, pos, valid, caches["rem"][j],
            block_tables=block_tables)

    x = apply_norm(cfg.norm, params["ln_f"], x)
    last = jnp.clip(lengths - 1, 0, c - 1)
    xl = x[jnp.arange(b), last][:, None, :]                  # [B,1,d]
    logits = _logits(cfg, params, xl)[:, 0]
    return logits, caches


def step_packed(cfg, params, caches, tokens, slot_id, pos, start, seg_len,
                block_tables=None):
    """Advance the engine by ONE token-packed ragged stream, in place —
    prefill chunks AND decode tokens ride the same call (unified ticks).

    tokens: [1,P] int32 — a single flat stream packing contiguous segments
    from up to B requests back-to-back: a prefilling request contributes
    its next prompt chunk, a running request contributes its one decode
    token as a length-1 segment (no per-slot padding, no separate decode
    dispatch); slot_id: [P] owning slot per token (-1 = dead pad); pos: [P]
    absolute position of each token within its own request; start/seg_len:
    [B] per-slot segment start and token count this call (the segment
    boundaries, cu_seqlens-style; a decode segment has ``start == its
    current position`` and ``seg_len == 1``).  ``block_tables`` ([B,M]
    int32, optional) routes attention K/V through the paged block store
    with a per-token scatter.  Returns (next-token logits [B,V] at each
    slot's last packed token — garbage for slots with no tokens this call —
    and the updated caches), so the caller samples every segment that
    completed a row this tick: prefill-finishers and decoders alike.

    Attention masks by segment id (:func:`~repro.models.layers
    .segment_attention`, the fused Pallas kernel family), so no token
    attends across requests — a length-1 decode segment sees exactly its
    own slot's history plus itself, which is the decode-attention
    predicate; recurrent blocks scatter the stream to the per-slot chunk
    layout and thread scan state through the state-in/state-out kernels (a
    length-1 segment is one scan step); MoE routes with the packed
    ``valid`` mask.  Calling this repeatedly over a workload is exact
    chunked prefill + decode for every supported family, with a jit cache
    of O(1) entries (one packed shape) instead of one per padded bucket
    plus a decode program."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"{cfg.name}: block pattern {cfg.block_pattern} "
                         "does not support packed prefill")
    prefix, pattern, n_groups, rem = _plan(cfg)
    x = params["embed"][tokens]

    for j, kind in enumerate(prefix):
        x, caches["prefix"][j], _ = B.block_apply_packed(
            cfg, kind, params["prefix"][j], x, pos, slot_id, start, seg_len,
            caches["prefix"][j], block_tables=block_tables)

    if n_groups:
        def group_body(x, xs):
            gp, gc = xs
            new_c = []
            for j, kind in enumerate(pattern):
                x, cj, _ = B.block_apply_packed(cfg, kind, gp[j], x, pos,
                                                slot_id, start, seg_len,
                                                gc[j],
                                                block_tables=block_tables)
                new_c.append(cj)
            return x, new_c

        x, new_groups = jax.lax.scan(
            group_body, x, (params["groups"], caches["groups"]))
        caches["groups"] = new_groups

    for j, kind in enumerate(rem):
        x, caches["rem"][j], _ = B.block_apply_packed(
            cfg, kind, params["rem"][j], x, pos, slot_id, start, seg_len,
            caches["rem"][j], block_tables=block_tables)

    x = apply_norm(cfg.norm, params["ln_f"], x)
    nslots = start.shape[0]
    t_idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    last_idx = jnp.max(
        jnp.where(slot_id[None, :]
                  == jnp.arange(nslots, dtype=jnp.int32)[:, None],
                  t_idx[None, :], -1), axis=1)                   # [B]
    xl = x[0, jnp.clip(last_idx, 0)][:, None, :]                 # [B,1,d]
    logits = _logits(cfg, params, xl)[:, 0]
    return logits, caches


# prefill-only packed streams are the decode-segment-free special case
prefill_packed = step_packed


def _is_pending(c) -> bool:
    return isinstance(c, dict) and "spec_stack" in c


def _resolve_pending(c, accept, spec_rows, *, grouped: bool):
    """Select the post-acceptance recurrent snapshot per spec row.

    ``spec_stack`` leaves are [L,B,...] (or [G,L,B,...] for scanned
    groups): snapshot ``j`` is the state after consuming offsets ``0..j``
    of the spec segment, so ``accept[b]`` names exactly the state after
    the last *emitted-and-consumed* token.  Non-spec rows keep the
    full-chunk result."""
    def pick(stack, full):
        if grouped:
            l = stack.shape[1]
            idx = jnp.clip(accept, 0, l - 1).reshape(
                (1, 1, -1) + (1,) * (stack.ndim - 3))
            sel = jnp.take_along_axis(stack, idx, axis=1)[:, 0]
            m = spec_rows.reshape((1, -1) + (1,) * (sel.ndim - 2))
        else:
            l = stack.shape[0]
            idx = jnp.clip(accept, 0, l - 1).reshape(
                (1, -1) + (1,) * (stack.ndim - 2))
            sel = jnp.take_along_axis(stack, idx, axis=0)[0]
            m = spec_rows.reshape((-1,) + (1,) * (sel.ndim - 1))
        return jnp.where(m, sel.astype(full.dtype), full)

    return jax.tree.map(pick, c["spec_stack"], c["spec_full"])


def step_spec(cfg, params, caches, tokens, slot_id, pos, start, seg_len,
              spec_rows, spec_idx, draft_len, block_tables=None):
    """One packed stream whose decode segments carry speculative drafts.

    Layout is :func:`step_packed`'s, except a running slot's segment is
    ``[pending, d1..dd]`` (length ``1 + d``, ``start = pos``): the pending
    token — the slot's last sampled, not-yet-consumed token — followed by
    ``d`` drafted continuations.  Extra inputs: spec_rows [B] bool marks
    draft-carrying rows; spec_idx [B, L] stream index of each segment
    offset (rows with shorter segments repeat their last index — masked by
    draft_len); draft_len [B] drafted tokens per row (0 for prefill rows,
    whose spec_idx[:, 0] names their last packed prompt token).

    Verification is the per-offset argmax over the SAME dispatch:
    ``m[b, j]`` is the model's next token after consuming offsets
    ``0..j``.  Greedy acceptance keeps the longest prefix of drafts that
    match: ``accept[b] = #{j >= 1 : drafts[1..j] all equal m[..j-1]}`` —
    the emitted tokens ``m[b, 0..accept[b]]`` are exactly what ``accept+1``
    sequential non-speculative steps would have produced, so speculation
    is token-identical by construction.  Returns (accept [B] int32,
    toks [B, L] int32 per-offset argmaxes, caches): the caller emits
    ``toks[b, :accept[b]+1]`` and re-bases the slot at
    ``start + accept + 1``.

    Rejected-suffix K/V needs no undo: dense entries at/after the next
    tick's ``start`` are position-masked as stale, paged entries are
    overwritten before the gather (write-then-gather) and causally hidden
    past the new frontier.  Recurrent state IS rolled back — spec rows
    advance through per-offset snapshots and the ``accept``-selected
    snapshot is written back here (:func:`blocks.block_apply_spec`)."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"{cfg.name}: block pattern {cfg.block_pattern} "
                         "does not support packed prefill")
    prefix, pattern, n_groups, rem = _plan(cfg)
    l_max = spec_idx.shape[1]
    x = params["embed"][tokens]

    for j, kind in enumerate(prefix):
        x, caches["prefix"][j], _ = B.block_apply_spec(
            cfg, kind, params["prefix"][j], x, pos, slot_id, start, seg_len,
            spec_rows, l_max, caches["prefix"][j],
            block_tables=block_tables)

    if n_groups:
        def group_body(x, xs):
            gp, gc = xs
            new_c = []
            for j, kind in enumerate(pattern):
                x, cj, _ = B.block_apply_spec(cfg, kind, gp[j], x, pos,
                                              slot_id, start, seg_len,
                                              spec_rows, l_max, gc[j],
                                              block_tables=block_tables)
                new_c.append(cj)
            return x, new_c

        x, new_groups = jax.lax.scan(
            group_body, x, (params["groups"], caches["groups"]))
        caches["groups"] = new_groups

    for j, kind in enumerate(rem):
        x, caches["rem"][j], _ = B.block_apply_spec(
            cfg, kind, params["rem"][j], x, pos, slot_id, start, seg_len,
            spec_rows, l_max, caches["rem"][j], block_tables=block_tables)

    x = apply_norm(cfg.norm, params["ln_f"], x)
    xs = x[0, spec_idx]                                     # [B, L, d]
    toks = jnp.argmax(_logits(cfg, params, xs), axis=-1).astype(jnp.int32)
    drafted = tokens[0, spec_idx]                           # [B, L]
    offs = jnp.arange(1, l_max, dtype=jnp.int32)[None, :]
    match = ((drafted[:, 1:] == toks[:, :-1])
             & (offs <= draft_len[:, None]))
    accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)

    # recurrent pending pairs -> the accept-selected canonical state tree
    for key in ("prefix", "rem"):
        if key in caches:
            caches[key] = [
                _resolve_pending(c, accept, spec_rows, grouped=False)
                if _is_pending(c) else c for c in caches[key]]
    if "groups" in caches:
        caches["groups"] = [
            _resolve_pending(c, accept, spec_rows, grouped=True)
            if _is_pending(c) else c for c in caches["groups"]]
    return accept, toks, caches


def decode_step(cfg, params, caches, token, pos, active=None,
                block_tables=None):
    """token: [B] int32; pos: [B] absolute position.  ``active`` ([B] bool,
    optional) masks cache/state writes for non-decoding slots.
    ``block_tables`` ([B,M] int32, optional) routes attention caches through
    the paged block store + paged decode kernel.  Returns
    (logits [B,V], caches')."""
    prefix, pattern, n_groups, rem = _plan(cfg)
    x = params["embed"][token][:, None, :]                # [B,1,d]
    if "pos_emb" in params:
        x = x + params["pos_emb"][pos][:, None, :]
    enc_out = caches.get("enc_out") if cfg.encoder_decoder else None
    layer_idx = 0

    def maybe_cross(x, li):
        if enc_out is None:
            return x
        cp = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
            t, li, 0, keepdims=False), params["cross"])
        h = apply_norm(cfg.norm, cp["ln"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, cp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wv"])
        s = jnp.einsum("bqhk,bshk->bhqs", q * (q.shape[-1] ** -0.5), _rep(k, q))
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqs,bshk->bqhk", p, _rep(v, q))
        return x + layers.attn_output(cp["attn"], o)

    def _rep(kv, q):
        g = q.shape[2] // kv.shape[2]
        return jnp.repeat(kv, g, axis=2) if g > 1 else kv

    for j, kind in enumerate(prefix):
        x, caches["prefix"][j], _ = B.block_apply_step(
            cfg, kind, params["prefix"][j], x, pos, caches["prefix"][j],
            active=active, block_tables=block_tables)
        x = maybe_cross(x, layer_idx)
        layer_idx += 1

    if n_groups:
        def group_body(carry, xs):
            x, li = carry
            gp, gc = xs
            new_c = []
            for j, kind in enumerate(pattern):
                x, cj, _ = B.block_apply_step(cfg, kind, gp[j], x, pos, gc[j],
                                              active=active,
                                              block_tables=block_tables)
                if enc_out is not None:
                    x = maybe_cross(x, li + j)
                new_c.append(cj)
            return (x, li + len(pattern)), new_c

        (x, layer_idx), new_groups = jax.lax.scan(
            group_body, (x, jnp.asarray(layer_idx, jnp.int32)),
            (params["groups"], caches["groups"]))
        caches["groups"] = new_groups

    for j, kind in enumerate(rem):
        x, caches["rem"][j], _ = B.block_apply_step(
            cfg, kind, params["rem"][j], x, pos, caches["rem"][j],
            active=active, block_tables=block_tables)
        x = maybe_cross(x, layer_idx)
        layer_idx += 1

    x = apply_norm(cfg.norm, params["ln_f"], x)
    logits = _logits(cfg, params, x)[:, 0]
    return logits, caches
