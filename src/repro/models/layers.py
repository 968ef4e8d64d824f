"""Model building blocks: norms, RoPE, GQA attention (full / windowed /
bidirectional / decode-with-cache), dense MLPs.

Pure functions over parameter pytrees.  Every init returns ``(params, axes)``
where ``axes`` is a parallel tree of :class:`~repro.distributed.sharding.Axes`
logical-name leaves used to derive PartitionSpecs.

Attention uses a query-chunked exact algorithm (lax.scan over query blocks)
above ``CHUNK_THRESHOLD`` so scores never materialize at [S, S] — the XLA
twin of the Pallas flash kernel in ``repro.kernels.flash_attention`` (which
replaces the inner computation on real TPUs; see kernels/*/ops.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import A, shard

CHUNK_THRESHOLD = 2048
Q_CHUNK = 512

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> tuple[dict, dict]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": A("embed")}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * params["scale"]


def layernorm_init(d: int, dtype) -> tuple[dict, dict]:
    return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
            {"scale": A("embed"), "bias": A("embed")})


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * params["scale"] + params["bias"]


def norm_init(kind: str, d: int, dtype):
    return rmsnorm_init(d, dtype) if kind == "rms" else layernorm_init(d, dtype)


def apply_norm(kind: str, params: dict, x: jax.Array) -> jax.Array:
    return rmsnorm(params, x) if kind == "rms" else layernorm(params, x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S] (absolute)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]   # [..., S, 1, half]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense projections
# ---------------------------------------------------------------------------


def _dense_init(key, shape, dtype, in_axis=-2):
    fan_in = shape[in_axis]
    scale = 1.0 / jnp.sqrt(fan_in)
    return (jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) * scale).astype(dtype)


def attention_init(key, cfg, *, cross: bool = False) -> tuple[dict, dict]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    # scaled by each projection's true fan-in (d in, h*hd out): scaling by a
    # head dim instead makes the scores hundreds wide, the softmax one-hot,
    # and every rounding difference a different attended key
    params = {
        "wq": _dense_init(ks[0], (d, h, hd), cfg.dtype, in_axis=0),
        "wk": _dense_init(ks[1], (d, kv, hd), cfg.dtype, in_axis=0),
        "wv": _dense_init(ks[2], (d, kv, hd), cfg.dtype, in_axis=0),
        "wo": _dense_init(ks[3], (h * hd, d), cfg.dtype).reshape(h, hd, d),
    }
    axes = {
        "wq": A("embed", "heads", None),
        "wk": A("embed", "kv_heads", None),
        "wv": A("embed", "kv_heads", None),
        "wo": A("heads", None, "embed"),
    }
    return params, axes


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B,Sq,H,D], k: [B,Sk,Kv,D] -> scores [B,H,Sq,Sk] without
    materializing repeated KV (GQA grouped einsum)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)       # [B,Kv,G,Sq,Sk]
    return s.reshape(b, h, sq, k.shape[1])


def _grouped_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: [B,H,Sq,Sk], v: [B,Sk,Kv,D] -> [B,Sq,H,D]."""
    b, h, sq, sk = p.shape
    kvh = v.shape[2]
    g = h // kvh
    pg = p.reshape(b, kvh, g, sq, sk)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pg, v)
    return o.reshape(b, sq, h, o.shape[-1])


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int) -> jax.Array:
    """[Sq, Sk] additive mask from absolute positions."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones(dq.shape[:1] + dk.shape[1:], bool)
    if causal:
        ok &= dk <= dq
    if window > 0:
        ok &= (dq - dk) < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_pos: jax.Array, k_pos: jax.Array,
              causal: bool = True, window: int = 0) -> jax.Array:
    """Exact attention, query-chunked above CHUNK_THRESHOLD.

    q [B,Sq,H,D] / k,v [B,Sk,Kv,D]; positions are 1-D absolute indices.
    window=0 means unbounded (full); window=W keeps |q-k| < W (SWA/local).

    REPRO_ATTN_IMPL=pallas (or pallas_interpret for CPU validation) routes
    standard self-attention through the differentiable Pallas flash kernels
    (fwd + custom_vjp bwd, kernels/flash_attention) — the on-TPU path.
    """
    import os
    impl = os.environ.get("REPRO_ATTN_IMPL", "xla")
    if impl.startswith("pallas") and q.shape[1] == k.shape[1]:
        from repro.kernels.flash_attention.vjp import flash_attention_grad
        out = flash_attention_grad(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal, window,
            impl == "pallas_interpret")
        return jnp.swapaxes(out, 1, 2)
    scale = q.shape[-1] ** -0.5
    sq = q.shape[1]
    if sq <= CHUNK_THRESHOLD or sq % Q_CHUNK != 0:
        s = _grouped_scores(q * scale, k).astype(jnp.float32)
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return _grouped_out(p, v)

    n_chunks = sq // Q_CHUNK
    qc = q.reshape(q.shape[0], n_chunks, Q_CHUNK, *q.shape[2:])
    qp = q_pos.reshape(n_chunks, Q_CHUNK)

    # Windowed kinds only ever attend to the trailing `window` positions:
    # slice K/V per q-chunk to [W + C] instead of scoring all S keys
    # (EXPERIMENTS.md SPerf gemma3: local layers are 5/6 of the stack, so
    # score traffic drops ~2-3x at 4k and ~8x at 32k prefill).
    kv_span = min(window + Q_CHUNK, k.shape[1]) if window > 0 else k.shape[1]
    chunk_starts = jnp.clip(
        (jnp.arange(n_chunks) + 1) * Q_CHUNK - kv_span, 0, k.shape[1] - kv_span)

    # flash-attention memory behaviour on the XLA path: remat the chunk body
    # so the backward recomputes scores per chunk from (q_i, k, v) instead of
    # materializing f32 [chunks, H, Cq, S] score tensors.
    @partial(jax.checkpoint, prevent_cse=False,
             policy=jax.checkpoint_policies.nothing_saveable)
    def body(_, inp):
        q_i, qp_i, start = inp
        k_i = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
        v_i = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
        kp_i = jax.lax.dynamic_slice_in_dim(k_pos, start, kv_span, axis=0)
        s = _grouped_scores(q_i * scale, k_i).astype(jnp.float32)
        s = s + _mask_bias(qp_i, kp_i, causal=causal, window=window)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return None, _grouped_out(p, v_i)

    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(qc, 1, 0), qp, chunk_starts))
    out = jnp.moveaxis(out, 0, 1)  # [B, n, C, H, D]
    return out.reshape(q.shape)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     k_pos: jax.Array, q_pos: jax.Array,
                     window: int = 0) -> jax.Array:
    """One-token attention against a cache.  q [B,1,H,D], caches [B,S,Kv,D].
    ``k_pos`` [B or 1, S] gives each slot's absolute position; unwritten or
    out-of-window slots are masked via position validity (pos >= 0).  The
    C=1 case of :func:`chunk_attention` — one masking implementation keeps
    decode and chunked prefill in exact agreement."""
    return chunk_attention(q, k_cache, v_cache, k_pos=k_pos,
                           q_pos=q_pos[:, None], window=window)


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    k_pos: jax.Array, q_pos: jax.Array,
                    window: int = 0) -> jax.Array:
    """Multi-token attention against per-row positioned keys (chunked
    prefill).  q [B,C,H,D]; k,v [B,N,Kv,D]; k_pos [B,N] absolute slot
    positions (-1 = unwritten); q_pos [B,C] absolute query positions.

    The causal/window structure is carried entirely by the position arrays,
    so the same code attends a prompt chunk against (prior-chunk cache ++
    in-chunk keys) with exact masking."""
    scale = q.shape[-1] ** -0.5
    s = _grouped_scores(q * scale, k).astype(jnp.float32)   # [B,H,C,N]
    valid = k_pos[:, None, :] >= 0                           # [B,C,N]
    valid &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window > 0:
        valid &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return _grouped_out(p, v)


def segment_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      q_pos: jax.Array, k_pos: jax.Array,
                      q_seg: jax.Array, k_seg: jax.Array,
                      window: int = 0) -> jax.Array:
    """Token-packed ragged attention (packed prefill).

    One flat stream carries chunks from *different* requests; every query
    and key names its owning segment, and a key is visible iff it belongs
    to the **same segment** (no cross-request attention), has been written
    (``k_pos >= 0``), is causal (``k_pos <= q_pos``), and sits inside the
    sliding window.  q [B,P,H,D]; k,v [B,N,Kv,D]; q_pos/q_seg [B,P];
    k_pos/k_seg [B,N] (segment id < 0 = dead pad: fully masked).

    The unmasked (segment, position) pairs are exactly the pairs the
    per-slot :func:`chunk_attention` path exposes, so packed and bucketed
    prefill agree up to summation order.  Fully-masked queries (dead pad
    lanes, or a live lane whose predicate admits no key) return exact
    zeros, so XLA-vs-Pallas parity holds on every lane.

    Dispatches through ``kernels/segment_attention`` (``REPRO_SEGMENT_IMPL``
    = ``xla`` | ``pallas`` | ``pallas_interpret``): the fused Pallas kernel
    runs an online softmax over K/V tiles with the same-segment / written /
    causal / window predicate fused into the tile mask, so the
    ``[B,H,P,N]`` score matrix never materializes."""
    # routed through the serving TP wrapper: head-sharded under an active
    # serve mesh (all-gathered back to the full head set in-body), the
    # plain fused op otherwise — bit-identical either way
    from repro.distributed.collectives import tp_segment_attention
    out = [tp_segment_attention(q[i], k[i], v[i], q_pos[i], k_pos[i],
                                q_seg[i], k_seg[i], window=window)
           for i in range(q.shape[0])]   # the packed stream is B == 1
    return jnp.stack(out).astype(q.dtype)


def attn_project_q(params, x, *, positions, theta):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    return rope(q, positions, theta)


def attn_project_kv(params, x, *, positions, theta):
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    return rope(k, positions, theta), v


def attn_output(params, o):
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, d_ff: int, kind: str, dtype) -> tuple[dict, dict]:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        params = {
            "w_gate": _dense_init(ks[0], (d, d_ff), dtype),
            "w_up": _dense_init(ks[1], (d, d_ff), dtype),
            "w_down": _dense_init(ks[2], (d_ff, d), dtype),
        }
        axes = {"w_gate": A("embed", "ff"), "w_up": A("embed", "ff"),
                "w_down": A("ff", "embed")}
    else:  # gelu
        params = {
            "w_up": _dense_init(ks[0], (d, d_ff), dtype),
            "w_down": _dense_init(ks[1], (d_ff, d), dtype),
        }
        axes = {"w_up": A("embed", "ff"), "w_down": A("ff", "embed")}
    return params, axes


def mlp(params: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    h = shard(h, "batch", "seq", "ff")
    return h @ params["w_down"]
