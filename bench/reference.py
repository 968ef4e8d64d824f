"""The plain reference the served tokens are judged against, and its
lower-precision control: the pieces every family's forward is written from
(``bench/families/<family>.py``), and the gap arithmetic.

A family's ``_logits`` is its published architecture written out in
``jax.numpy`` at float32 (``jax.default_matmul_precision("highest")``).  It
imports nothing of the program and reads only the weights the benchmark
made.  Departures from the published models (noted in each configuration
file) are shared with the program.

``control=True`` is the same forward computed one precision step below the
configuration's bfloat16: every linear layer (``linear``) takes fp8 (e4m3)
operands, weights scaled per output channel and activations per token,
accumulated in float32.  (int8 with the same scales was tried first and
reads within 1.6 times the served path's gaps on yi-6b, and 0 on some
starcoder2 seeds: it does not separate.)

A sequence is padded to one fixed length, and its served tokens to one
fixed count, so each cell compiles one program per precision whatever the
request; causal attention keeps the padding out of every position that is
read.  Queries run in blocks, so the reference fits beside the served
weights."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def quant(x, axis):
    """fp8 (e4m3) fake-quantization scaled along ``axis`` (the contraction
    axis): exact fp8 values times one float32 scale per remaining index."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def linear(x, w, spec, contract_w, control):
    """``einsum(spec, x, w)`` contracting the last axis of ``x`` with axes
    ``contract_w`` of ``w``."""
    w = w.astype(jnp.float32)
    if control:
        x = quant(x, -1)
        w = quant(w, contract_w)
    return jnp.einsum(spec, x, w)


def norm(s, p, x):
    if s.norm == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s.norm_eps)
        return y * p["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + s.norm_eps)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def rope(x, pos, theta):
    """x: [S, heads, D]; rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(s, q, k, v):
    """Causal GQA attention, queries in blocks: q [S, H, D], k/v [S, Kv, D]."""
    n = q.shape[0]
    g = s.heads // s.kv_heads
    qg = q.reshape(n, s.kv_heads, g, s.head_dim) * s.head_dim ** -0.5
    kpos = jnp.arange(n)
    outs = []
    for lo in range(0, n, Q_BLOCK):
        qb = qg[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k)
        qpos = lo + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", pr, v))
    return jnp.concatenate(outs).reshape(n, s.heads, s.head_dim)


@functools.partial(jax.jit, static_argnames=("logits", "s"))
def _gaps(logits, s, weights, tokens, idx, judged):
    ref = logits(s, weights, tokens, idx, control=False)
    return ref.max(axis=1) - jnp.take_along_axis(ref, judged[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("logits", "s"))
def _control_best(logits, s, weights, tokens, idx):
    return logits(s, weights, tokens, idx, control=True).argmax(axis=1)


def served_gaps(logits, s, weights, prompt, served, *, length: int,
                rows: int, control: bool = False) -> np.ndarray:
    """For each served token: the reference's largest logit at that
    position less its logit of the token served there (0 where they agree).
    With ``control`` the token judged is the one the control puts first at
    that position, given the same prompt and served tokens.  The sequence
    is padded to ``length`` positions and the served tokens to ``rows``.
    ``logits(s, weights, tokens, idx, *, control)`` is the family's jitted
    forward, read at the rows ``idx`` of the padded sequence."""
    n = len(served)
    seq = np.zeros((length,), np.int32)
    seq[:len(prompt) + n - 1] = np.concatenate(
        [np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    # position p predicts token p + 1: the first served token comes from
    # the prompt's last position
    idx = np.zeros((rows,), np.int32)
    idx[:n] = len(prompt) - 1 + np.arange(n)
    tok = np.zeros((rows,), np.int32)
    tok[:n] = served
    seq, idx, tok = jnp.asarray(seq), jnp.asarray(idx), jnp.asarray(tok)
    with jax.default_matmul_precision("highest"):
        if control:
            tok = _control_best(logits, s, weights, seq, idx)
        gaps = _gaps(logits, s, weights, seq, idx, tok)
    return np.asarray(gaps)[:n]
