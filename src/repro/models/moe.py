"""Fine-grained Mixture-of-Experts layer (DeepSeekMoE / Llama-4 style).

Design notes (roofline-honest):
  * shared experts always-on + routed experts top-k, softmax-renormalized.
  * capacity-based dispatch via **gather/scatter**, not GShard one-hot
    einsums: a [T,E,C] one-hot matmul would dominate compiled FLOPs by >100x
    over the expert GEMMs and poison the roofline's compute term.  Instead we
    compute each assignment's position-in-expert with a cumsum, scatter token
    ids into [G, E, C] slot tables, gather tokens, run batched expert GEMMs
    ([E, C, d] x [E, d, m]), and gather back — FLOPs = active-expert GEMMs
    only, as deployed MoE kernels achieve.
  * tokens are processed in fixed GROUPS along the sequence (<=512 tokens) so
    the slot tables stay small and shard over the data axes; capacity is per
    group: C = ceil(group * top_k / E * capacity_factor).  Overflow tokens
    drop to the shared path only (standard capacity-drop semantics).
  * expert dim shards over 'model' (EP); GSPMD inserts the all-to-alls.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import A, shard
from .layers import _dense_init

GROUP_TOKENS = 512


def moe_init(key, cfg) -> tuple[dict, dict]:
    d, e, m = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    params = {
        "router": _dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": _dense_init(ks[1], (e, d, m), cfg.dtype),
        "w_up": _dense_init(ks[2], (e, d, m), cfg.dtype),
        "w_down": _dense_init(ks[3], (e, m, d), cfg.dtype),
    }
    axes = {
        "router": A("embed", "experts"),
        "w_gate": A("experts", "embed", "moe_ff"),
        "w_up": A("experts", "embed", "moe_ff"),
        "w_down": A("experts", "moe_ff", "embed"),
    }
    if cfg.num_shared_experts:
        ms = cfg.moe_d_ff * cfg.num_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        params["shared"] = {
            "w_gate": _dense_init(ks2[0], (d, ms), cfg.dtype),
            "w_up": _dense_init(ks2[1], (d, ms), cfg.dtype),
            "w_down": _dense_init(ks2[2], (ms, d), cfg.dtype),
        }
        axes["shared"] = {"w_gate": A("embed", "ff"), "w_up": A("embed", "ff"),
                          "w_down": A("ff", "embed")}
    return params, axes


def _group_shape(batch: int, seq: int) -> tuple[int, int]:
    g_tokens = min(GROUP_TOKENS, seq)
    while seq % g_tokens:
        g_tokens -= 1
    return batch * (seq // g_tokens), g_tokens


def _valid_cap(nv, cap: int, cfg):
    """Drop threshold for a group with ``nv`` REAL tokens (traced scalar or
    [G] vector): capacity scales with the valid-token count so padding can
    neither steal nor inflate expert capacity.  ``cap`` (static, computed
    over the padded group size) stays the slot-table shape and upper bound."""
    cap_v = jnp.ceil(nv.astype(jnp.float32) * cfg.top_k / cfg.num_experts
                     * cfg.capacity_factor)
    return jnp.clip(cap_v.astype(jnp.int32), 1, cap)


def moe_apply(params: dict, x: jax.Array, cfg, *, return_aux: bool = False,
              valid: jax.Array | None = None):
    """x: [B, S, d] -> [B, S, d] (+ aux load-balance loss scalar).

    ``valid`` ([B, S] bool, optional) marks real tokens in a padded chunk
    (chunked prefill / masked decode): invalid tokens are excluded from the
    position-in-expert count AND the per-group capacity is clamped to
    ``ceil(n_valid * k / e * capacity_factor)``, so pads neither steal nor
    inflate expert capacity — capacity is computed over valid tokens.  Note
    that under capacity *overflow* the drop pattern still depends on how
    tokens are grouped (a chunked prompt is dispatched in chunk-sized
    groups, the one-shot path in up-to-``GROUP_TOKENS`` groups), so chunked
    and one-shot prefill are token-identical only when routing is drop-free
    (ample ``capacity_factor``; serving keeps drops exceptional)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    g, gt = _group_shape(b, s)
    cap = max(1, math.ceil(gt * k / e * cfg.capacity_factor))

    xg = x.reshape(g, gt, d)
    xg = shard(xg, "batch", None, "embed")

    logits = (xg.astype(jnp.float32) @ params["router"])          # [G,T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                        # [G,T,K]
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each assignment inside its expert (token-major priority)
    flat_i = top_i.reshape(g, gt * k)                             # [G,TK]
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32)           # [G,TK,E]
    if valid is not None:
        # token-major repeat matches flat_i's [T, K] -> [T*K] layout
        valid_flat = jnp.repeat(valid.reshape(g, gt), k, axis=1)  # [G,TK]
        onehot = onehot * valid_flat[..., None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - onehot                     # prior count
    pos = jnp.take_along_axis(pos, flat_i[..., None], axis=2)[..., 0]  # [G,TK]
    if valid is None:
        keep = pos < cap
    else:
        cap_v = _valid_cap(valid.reshape(g, gt).sum(axis=1), cap, cfg)
        keep = valid_flat & (pos < cap_v[:, None])

    # slot tables: token index per (expert, capacity) slot
    token_ids = jnp.tile(jnp.arange(gt, dtype=jnp.int32)[:, None], (1, k)) \
        .reshape(gt * k)
    slot_tok = jnp.full((g, e, cap), gt, jnp.int32)   # gt = "no token" sentinel

    def fill(slot, fi, p, kp, tid):
        fi = jnp.where(kp, fi, e)       # overflow -> dropped via index clip
        p = jnp.where(kp, p, cap)
        return slot.at[fi, p].set(tid, mode="drop")

    slot_tok = jax.vmap(fill)(slot_tok, flat_i, pos, keep,
                              jnp.broadcast_to(token_ids, (g, gt * k)))

    # gather tokens into expert slots ([G,E,C,d]); sentinel rows read zeros
    xg_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    expert_in = jnp.take_along_axis(
        xg_pad[:, None, :, :],                       # [G,1,T+1,d]
        slot_tok[..., None].clip(0, gt),             # [G,E,C,1]
        axis=2)                                      # [G,E,C,d]
    expert_in = shard(expert_in, "batch", "experts", None, "embed")

    # batched expert GEMMs (EP: expert dim on 'model')
    h = jnp.einsum("gecd,edm->gecm", expert_in, params["w_gate"])
    u = jnp.einsum("gecd,edm->gecm", expert_in, params["w_up"])
    act = jax.nn.silu(h) * u
    expert_out = jnp.einsum("gecm,emd->gecd", act, params["w_down"])
    expert_out = shard(expert_out, "batch", "experts", None, "embed")

    # combine: gather each assignment's slot output, weight, sum over k
    flat_pos = pos.reshape(g, gt, k)
    flat_keep = keep.reshape(g, gt, k)
    gather_idx = (top_i * cap + flat_pos).clip(0, e * cap - 1)    # [G,T,K]
    eo_flat = expert_out.reshape(g, e * cap, d)
    picked = jnp.take_along_axis(
        eo_flat[:, None, :, :],                      # [G,1,EC,d]
        gather_idx[..., None],                       # [G,T,K,1]
        axis=2)                                      # [G,T,K,d]
    w = (top_p * flat_keep).astype(picked.dtype)[..., None]
    routed = (picked * w).sum(axis=2)                # [G,T,d]
    out = routed

    if "shared" in params:
        sh = params["shared"]
        hs = jax.nn.silu(xg @ sh["w_gate"]) * (xg @ sh["w_up"])
        out = out + hs @ sh["w_down"]

    out = out.reshape(b, s, d)
    if not return_aux:
        return out
    # load-balance aux loss (Switch style): E * sum_e f_e * p_e
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    mean_probs = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * mean_probs)
    return out, aux


def moe_reference(params: dict, x: jax.Array, cfg) -> jax.Array:
    """Oracle: per-token loop over selected experts (no capacity drops when
    capacity is ample).  Used by tests only."""
    b, s, d = x.shape
    probs = jax.nn.softmax(x.astype(jnp.float32) @ params["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(x)
    for kk in range(cfg.top_k):
        idx = top_i[..., kk]                                  # [B,S]
        wg = params["w_gate"][idx]                            # [B,S,d,m]
        wu = params["w_up"][idx]
        wd = params["w_down"][idx]
        h = jax.nn.silu(jnp.einsum("bsd,bsdm->bsm", x, wg)) * \
            jnp.einsum("bsd,bsdm->bsm", x, wu)
        y = jnp.einsum("bsm,bsmd->bsd", h, wd)
        out = out + y * top_p[..., kk][..., None].astype(x.dtype)
    if "shared" in params:
        sh = params["shared"]
        hs = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
        out = out + hs @ sh["w_down"]
    return out


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (beyond-paper optimization)
# ---------------------------------------------------------------------------
#
# The gather/scatter dispatch above is correct but GSPMD partitions it into
# all-reduces of full activation tensors (measured: 349 GB/chip/step on
# deepseek-moe train_4k — the collective-bound cell).  This version pins the
# communication pattern explicitly: tokens stay sharded over the data axes,
# experts over 'model'; each device runs only its local experts over its
# local tokens and ONE psum over 'model' combines the top-k contributions —
# the minimal EP collective (activation-sized, not dispatch-table-sized).


def _moe_local(router, w_gate, w_up, w_down, x_loc, valid_loc, *, cfg,
               e_local, axis_name):
    """Per-shard body: x_loc [B_loc, S, d]; valid_loc [B_loc, S] bool;
    w_* [E_local, d, m]."""
    b, s, d = x_loc.shape
    k = cfg.top_k
    e = cfg.num_experts
    t = b * s
    xt = x_loc.reshape(t, d)
    logits = xt.astype(jnp.float32) @ router              # full router [d, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                # global expert ids
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    shard = jax.lax.axis_index(axis_name)
    e0 = shard * e_local
    cap = max(1, math.ceil(t * k / e * cfg.capacity_factor))

    # assignments targeting LOCAL experts only (invalid/pad tokens excluded
    # from the slot count so they cannot steal capacity)
    flat_i = top_i.reshape(t * k)
    local_i = flat_i - e0                                 # [TK] in [0, e_local)
    is_local = (local_i >= 0) & (local_i < e_local)
    is_local &= jnp.repeat(valid_loc.reshape(t), k)
    onehot = jax.nn.one_hot(jnp.where(is_local, local_i, e_local),
                            e_local + 1, dtype=jnp.int32)[:, :e_local]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(
        pos, jnp.clip(local_i, 0, e_local - 1)[:, None], axis=1)[:, 0]
    # drop threshold scales with the REAL token count (see _valid_cap)
    keep = is_local & (pos < _valid_cap(valid_loc.sum(), cap, cfg))

    token_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    slot_tok = jnp.full((e_local, cap), t, jnp.int32)
    slot_tok = slot_tok.at[
        jnp.where(keep, local_i, e_local),
        jnp.where(keep, pos, cap)].set(token_ids, mode="drop")

    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    expert_in = xt_pad[slot_tok.clip(0, t)]               # [E_loc, C, d]
    h = jnp.einsum("ecd,edm->ecm", expert_in, w_gate)
    u = jnp.einsum("ecd,edm->ecm", expert_in, w_up)
    act = jax.nn.silu(h) * u
    expert_out = jnp.einsum("ecm,emd->ecd", act, w_down)  # [E_loc, C, d]

    # combine local contributions, then ONE activation psum over 'model'
    gather_idx = (jnp.clip(local_i, 0, e_local - 1) * cap
                  + jnp.clip(pos, 0, cap - 1))
    picked = expert_out.reshape(e_local * cap, d)[gather_idx]   # [TK, d]
    w = (top_p.reshape(t * k) * keep).astype(picked.dtype)
    routed = jnp.zeros((t, d), picked.dtype).at[token_ids].add(
        picked * w[:, None])
    routed = jax.lax.psum(routed, axis_name)
    return routed.reshape(b, s, d)


def moe_apply_ep(params: dict, x: jax.Array, cfg, *, return_aux: bool = False,
                 valid: jax.Array | None = None):
    """shard_map expert-parallel MoE.  Falls back to :func:`moe_apply` when
    no mesh with a 'model' axis is active or experts don't divide it.
    ``valid`` masks pad tokens out of the capacity count (chunked prefill)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_experts % mesh.shape["model"]
            or (x.shape[0] % _dp_size(mesh) and x.shape[0] != 1)):
        return moe_apply(params, x, cfg, return_aux=return_aux, valid=valid)
    e_local = cfg.num_experts // mesh.shape["model"]
    if x.shape[0] % _dp_size(mesh):
        # the serve engine's token-packed stream is one [1, P] batch row —
        # indivisible by any real data axis, but EP still pays: replicate
        # the tokens over the data axes and shard only the experts
        dp_axes: tuple = ()
    else:
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    if valid is None:
        valid = jnp.ones(x.shape[:2], bool)
    fn = jax.shard_map(
        partial(_moe_local, cfg=cfg, e_local=e_local, axis_name="model"),
        mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(batch_spec, None, None),
                  P(batch_spec, None)),
        out_specs=P(batch_spec, None, None),
    )
    out = fn(params["router"], params["w_gate"], params["w_up"],
             params["w_down"], x, valid)

    if "shared" in params:
        sh = params["shared"]
        hs = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
        hs = shard(hs, "batch", "seq", "ff")
        out = out + hs @ sh["w_down"]
    if not return_aux:
        return out
    # aux load-balance loss computed on the (cheap, replicated) router pass
    probs = jax.nn.softmax(x.astype(jnp.float32) @ params["router"], axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.num_experts, dtype=jnp.float32),
                    axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(frac * probs.mean(axis=(0, 1)))
    return out, aux


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _moe_local_serve(router, w_gate, w_up, w_down, x_loc, valid_loc, *, cfg,
                     e_local, dp_axes):
    """Decode-path shard body: expert weights stay RESIDENT, 2D-sharded
    (experts x moe_ff); the (few) decode tokens are all-gathered instead.
    Collectives per layer = O(tokens * d), not O(weights)."""
    b_loc, s, d = x_loc.shape
    k = cfg.top_k
    e = cfg.num_experts
    # gather the token batch over the data axes (tiny at decode)
    x_all = x_loc
    valid_all = valid_loc
    for ax in dp_axes:
        x_all = jax.lax.all_gather(x_all, ax, axis=0, tiled=True)
        valid_all = jax.lax.all_gather(valid_all, ax, axis=0, tiled=True)
    t = x_all.shape[0] * s
    xt = x_all.reshape(t, d)
    logits = xt.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    shard_idx = jax.lax.axis_index("model")
    e0 = shard_idx * e_local
    cap = max(1, math.ceil(t * k / e * cfg.capacity_factor))

    # inactive-slot tokens are excluded from the capacity count, and the
    # drop threshold scales with the REAL token count (see moe_apply)
    flat_i = top_i.reshape(t * k)
    local_i = flat_i - e0
    is_local = (local_i >= 0) & (local_i < e_local)
    is_local &= jnp.repeat(valid_all.reshape(t), k)
    onehot = jax.nn.one_hot(jnp.where(is_local, local_i, e_local),
                            e_local + 1, dtype=jnp.int32)[:, :e_local]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(
        pos, jnp.clip(local_i, 0, e_local - 1)[:, None], axis=1)[:, 0]
    keep = is_local & (pos < _valid_cap(valid_all.sum(), cap, cfg))

    token_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    slot_tok = jnp.full((e_local, cap), t, jnp.int32)
    slot_tok = slot_tok.at[
        jnp.where(keep, local_i, e_local),
        jnp.where(keep, pos, cap)].set(token_ids, mode="drop")

    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    expert_in = xt_pad[slot_tok.clip(0, t)]               # [E_loc, C, d]
    h = jnp.einsum("ecd,edm->ecm", expert_in, w_gate)     # m = local slice
    u = jnp.einsum("ecd,edm->ecm", expert_in, w_up)
    act = jax.nn.silu(h) * u
    expert_out = jnp.einsum("ecm,emd->ecd", act, w_down)  # partial over m

    gather_idx = (jnp.clip(local_i, 0, e_local - 1) * cap
                  + jnp.clip(pos, 0, cap - 1))
    picked = expert_out.reshape(e_local * cap, d)[gather_idx]
    w = (top_p.reshape(t * k) * keep).astype(picked.dtype)
    routed = jnp.zeros((t, d), picked.dtype).at[token_ids].add(
        picked * w[:, None])
    # sum m-partials over data AND expert contributions over model
    routed = jax.lax.psum(routed, ("model",) + tuple(dp_axes))
    # slice back this shard's batch
    didx = jnp.zeros((), jnp.int32)
    mult = 1
    for ax in reversed(dp_axes):
        didx = didx + jax.lax.axis_index(ax) * mult
        mult = mult * jax.lax.psum(1, ax)
    start = didx * b_loc
    routed = jax.lax.dynamic_slice_in_dim(routed.reshape(x_all.shape[0], s, d),
                                          start, b_loc, axis=0)
    return routed


def moe_apply_ep_serve(params: dict, x: jax.Array, cfg,
                       valid: jax.Array | None = None):
    """Decode-time EP: resident weights, token gather (see _moe_local_serve).
    ``valid`` ([B, S] bool) masks inactive decode slots out of the capacity
    count so a free slot's stale token can't steal an expert slot."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    dp_axes = tuple(a for a in ("pod", "data") if a in (mesh.axis_names if mesh else ()))
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_experts % mesh.shape["model"]
            or cfg.moe_d_ff % _dp_size(mesh)
            or x.shape[0] % _dp_size(mesh)):
        return moe_apply(params, x, cfg, valid=valid)
    e_local = cfg.num_experts // mesh.shape["model"]
    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    dspec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    if valid is None:
        valid = jnp.ones(x.shape[:2], bool)
    fn = jax.shard_map(
        partial(_moe_local_serve, cfg=cfg, e_local=e_local, dp_axes=dp_axes),
        mesh=mesh,
        in_specs=(P(), P("model", None, dspec), P("model", None, dspec),
                  P("model", dspec, None), P(batch_spec, None, None),
                  P(batch_spec, None)),
        out_specs=P(batch_spec, None, None),
    )
    out = fn(params["router"], params["w_gate"], params["w_up"],
             params["w_down"], x, valid)
    if "shared" in params:
        sh = params["shared"]
        hs = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
        out = out + hs @ sh["w_down"]
    return out
