"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

    h_t = exp(log_a_t) * h_{t-1} + b_t

Grid = (batch, feature_blocks, time_chunks) with time innermost/sequential;
the carried hidden state for the current (batch, feature-block) persists in
VMEM scratch.  Within a chunk the recurrence unrolls as a fori_loop over
rows — each step is a fused VPU multiply-add over the feature block, with all
chunk data resident in VMEM (one HBM read per element, the minimum).

``rglru_scan_state`` is the state-in/state-out variant: the scratch is
seeded from a caller-provided h0 [B, F] and the post-sequence state comes
back as a second output — the scan-state ABI chunked prefill threads across
per-row chunk boundaries (see kernels/README.md).  ``rglru_scan`` is the
zero-init wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
BLOCK_F = 512


def _slab_rows(chunk: int) -> int:
    return next((r for r in (16, 8) if chunk % r == 0), chunk)


def _kernel(loga_ref, b_ref, h0_ref, h_ref, hout_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    # the chunk is walked in slabs of `slab` rows at aligned offsets: each
    # slab is loaded once, its rows stepped in order, and the results
    # gathered into one slab store (Mosaic refuses unaligned row access)
    slab = _slab_rows(chunk)
    rid = jax.lax.broadcasted_iota(jnp.int32, (slab, h_scr.shape[1]), 0)

    def step(si, h):                           # h: [1, F]
        rows = pl.ds(pl.multiple_of(si * slab, slab), slab)
        a = jnp.exp(loga_ref[0, rows, :].astype(jnp.float32))   # [slab, F]
        x = b_ref[0, rows, :].astype(jnp.float32)
        out = jnp.zeros_like(x)
        for t in range(slab):
            h = a[t:t + 1] * h + x[t:t + 1]
            out = jnp.where(rid == t, h, out)
        h_ref[0, rows, :] = out.astype(h_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // slab, step, h_scr[...])
    hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_f", "interpret"))
def rglru_scan_state(log_a: jax.Array, b: jax.Array, h0: jax.Array, *,
                     chunk: int = CHUNK, block_f: int = BLOCK_F,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """log_a, b: [B, S, F]; h0: [B, F] f32 carried state.
    Returns (h [B, S, F], h_out [B, F] f32)."""
    bsz, s, f = log_a.shape
    # state as [B, 1, F] so its (1, block_f) trailing block is a legal tile
    h0 = h0.astype(jnp.float32)[:, None, :]
    chunk = min(chunk, s)
    block_f = min(block_f, f)
    assert s % chunk == 0 and f % block_f == 0
    grid = (bsz, f // block_f, s // chunk)
    h, h_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_f), lambda b_, fi, ci: (b_, ci, fi)),
            pl.BlockSpec((1, chunk, block_f), lambda b_, fi, ci: (b_, ci, fi)),
            pl.BlockSpec((1, 1, block_f), lambda b_, fi, ci: (b_, 0, fi)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_f),
                         lambda b_, fi, ci: (b_, ci, fi)),
            pl.BlockSpec((1, 1, block_f), lambda b_, fi, ci: (b_, 0, fi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, f), b.dtype),
            jax.ShapeDtypeStruct((bsz, 1, f), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_f), jnp.float32)],
        interpret=interpret,
    )(log_a, b, h0)
    return h, h_out[:, 0]


@functools.partial(jax.jit, static_argnames=("chunk", "block_f", "interpret"))
def rglru_scan(log_a: jax.Array, b: jax.Array, *, chunk: int = CHUNK,
               block_f: int = BLOCK_F, interpret: bool = False) -> jax.Array:
    """log_a, b: [B, S, F] -> h: [B, S, F] with h_{-1} = 0 (zero init)."""
    h0 = jnp.zeros(log_a.shape[::2], jnp.float32)
    return rglru_scan_state(log_a, b, h0, chunk=chunk, block_f=block_f,
                            interpret=interpret)[0]
