"""Pallas TPU kernel for the RWKV-6 chunked recurrence (time mix core).

Grid = (batch * heads, time_chunks), chunks innermost/sequential; the running
state matrix S [N, N] persists in VMEM scratch across chunk steps.  Per chunk
of length L the kernel computes (all f32 in VMEM):

    cum_t   = cumsum(log w)                      [L, N]
    y_intra = r_t . sum_{s<t} exp(cum_t - cum_s) k_s v_s^T   (strict lower)
    y_diag  = (r_t * u * k_t) . v_t
    y_cross = (r_t * exp(cum_t)) @ S
    S'      = diag(exp(cum_L)) S + sum_s exp(cum_L - cum_s) (k_s o v_s)

which is exactly ``models.rwkv6.time_mix_chunked``'s math; the oracle in
``ref.py`` is the naive per-token recurrence both are tested against.

``rwkv6_scan_state`` is the state-in/state-out variant: S is seeded from a
caller-provided matrix and the post-sequence state is returned as a second
output — the scan-state ABI chunked prefill threads across per-row chunk
boundaries (see kernels/README.md).  ``rwkv6_scan`` is the zero-init wrapper.

The intra-chunk term contracts over (s, i) per output channel j; with L = 32
and N = 64 the working set is MXU/VPU friendly and S stays resident, so HBM
traffic is just the r/k/v/w chunk streams — the operational-intensity win the
chunked schedule exists for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_DIM = 64
CHUNK = 32


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, *, chunk: int):
    ci = pl.program_id(1)
    n = s_scr.shape[0]

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)       # [L, N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = w_ref[0].astype(jnp.float32)      # log decay, [L, N]
    u = u_ref[0].astype(jnp.float32)       # [1, N] bonus

    # prefix sums as matmuls with constant masks (Mosaic has no cumsum):
    # cum[t] = sum_{u<=t} lw_u, inclusive; tot[i, j] = sum_u lw_u[i], the
    # chunk's total log decay of state row i, broadcast over columns j
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general((row >= col).astype(jnp.float32), lw,
                              (((1,), (0,)), ((), ())), precision=hi,
                              preferred_element_type=jnp.float32)  # [L, N]
    tot = jax.lax.dot_general(lw, jnp.ones((chunk, n), jnp.float32),
                              (((0,), (0,)), ((), ())), precision=hi,
                              preferred_element_type=jnp.float32)  # [N, N]
    cum_last = jnp.sum(lw, axis=0, keepdims=True)                  # [1, N]
    ecum = cum - lw                        # exclusive: sum_{u<t} lw_u
    A = jnp.exp(ecum)                      # decay applied to the r-side read

    # scores[t, s] = sum_i r[t,i] k[s,i] prod_{s<u<t} w_u[i]
    #              = sum_i r[t,i] k[s,i] exp(ecum[t,i] - cum[s,i]), t > s,
    # built one key column at a time from 2-D tiles.  The exponent is <= 0
    # wherever t > s; clamping it keeps the masked entries finite.
    scores = jnp.zeros((chunk, chunk), jnp.float32)
    for j in range(chunk):
        decay = jnp.exp(jnp.minimum(ecum - cum[j:j + 1], 0.0))    # [L, N]
        col_j = jnp.sum(r * decay * k[j:j + 1], axis=1, keepdims=True)
        scores = jnp.where(col == j, col_j, scores)
    scores = jnp.where(row > col, scores, 0.0)                    # [L, L]
    y_intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_diag = jnp.sum(r * u * k, axis=1, keepdims=True) * v     # [L, N]
    y_cross = jax.lax.dot_general(r * A, s_scr[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    decay_k = jnp.exp(cum_last - cum) * k                      # [L, N]
    s_scr[...] = jnp.exp(tot) * s_scr[...] + jax.lax.dot_general(
        decay_k, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = (y_intra + y_diag + y_cross).astype(y_ref.dtype)
    sout_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_state(r: jax.Array, k: jax.Array, v: jax.Array,
                     logw: jax.Array, u: jax.Array, s0: jax.Array, *,
                     chunk: int = CHUNK,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r,k,v,logw: [BH, S, N]; u: [BH, N]; s0: [BH, N, N] f32 carried state.
    Returns (y [BH, S, N], s_out [BH, N, N] f32).

    BH = batch * heads flattened; S must be a multiple of ``chunk``."""
    bh, s, n = r.shape
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    grid = (bh, s // chunk)
    u2 = u[:, None, :]
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, n), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, n, n), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, n), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u2, s0.astype(jnp.float32))
    return y, s_out


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
               u: jax.Array, *, chunk: int = CHUNK,
               interpret: bool = False) -> jax.Array:
    """Zero-init-state wrapper: r,k,v,logw [BH, S, N]; u [BH, N] -> y."""
    bh, _, n = r.shape
    s0 = jnp.zeros((bh, n, n), jnp.float32)
    return rwkv6_scan_state(r, k, v, logw, u, s0, chunk=chunk,
                            interpret=interpret)[0]
