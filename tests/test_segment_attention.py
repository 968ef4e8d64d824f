"""Fused segment-attention kernel family vs. the ref oracle, and the
dead-pad-lane contract.

The correctness bar for the unified-tick path: the Pallas kernels (run in
interpreter mode on CPU) must match ``ref.py`` on EVERY lane — live and
dead — over ragged segment mixes, GQA/MQA head layouts, sliding windows
(the gemma3 swa kind), bf16 streams, and out-of-order / holey paged block
tables.  Exact all-lane parity is only possible because fully-masked
queries emit exact zeros instead of a garbage uniform softmax (the
sensor-honesty satellite on ``layers.segment_attention``)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.segment_attention import (
    paged_segment_attention, paged_segment_attention_ref,
    segment_attention, segment_attention_ref, segment_attention_op)
from repro.kernels.segment_attention.segment_attention import (
    kv_chunk_blocks, live_block_ranges, live_blocks)
from repro.models import layers, zoo
from repro.serve import Request, ServeEngine


def _ragged_stream(rng, p, n, n_seg, max_pos=64):
    """Packed-ABI tags: contiguous query segments (with a dead tail) and
    shuffled keys carrying (pos, seg) pairs, some unwritten (-1)."""
    q_seg = np.full((p,), -1, np.int32)
    q_pos = np.zeros((p,), np.int32)
    cursor = 0
    for s in range(n_seg):
        ln = int(rng.integers(1, max(2, (p - cursor) // max(1, n_seg - s))))
        if cursor + ln > p:
            break
        start = int(rng.integers(0, max_pos - ln))
        q_seg[cursor:cursor + ln] = s
        q_pos[cursor:cursor + ln] = np.arange(start, start + ln)
        cursor += ln
    k_seg = rng.integers(-1, n_seg, n).astype(np.int32)
    k_pos = rng.integers(-1, max_pos, n).astype(np.int32)
    return (jnp.asarray(q_pos), jnp.asarray(q_seg),
            jnp.asarray(k_pos), jnp.asarray(k_seg))


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_kernel_matches_ref(rng, h, kv, window, dtype):
    p, n, d = 37, 101, 16
    q = jnp.asarray(rng.standard_normal((p, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, kv, d)), dtype)
    q_pos, q_seg, k_pos, k_seg = _ragged_stream(rng, p, n, 3)
    ref = segment_attention_ref(q, k, v, q_pos, k_pos, q_seg, k_seg,
                                window=window)
    got = segment_attention(q, k, v, q_pos, k_pos, q_seg, k_seg,
                            window=window, block_q=16, block_k=32,
                            interpret=True)
    # all-lane comparison: dead lanes are exact zeros on both sides
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, f"h={h} kv={kv} w={window}: {err:.2e}"
    dead = np.asarray(q_seg) < 0
    assert dead.any()
    assert (np.asarray(ref, np.float32)[dead] == 0.0).all()
    assert (np.asarray(got, np.float32)[dead] == 0.0).all()


def test_segment_kernel_fully_masked_live_lane(rng):
    """A live lane whose predicate admits no key (nothing written yet) must
    also emit exact zeros — kernel and oracle alike."""
    p, n, h, d = 8, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((p, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
    q_pos = jnp.arange(p, dtype=jnp.int32)
    q_seg = jnp.zeros((p,), jnp.int32)
    k_pos = jnp.full((n,), -1, jnp.int32)        # nothing written
    k_seg = jnp.zeros((n,), jnp.int32)
    ref = segment_attention_ref(q, k, v, q_pos, k_pos, q_seg, k_seg)
    got = segment_attention(q, k, v, q_pos, k_pos, q_seg, k_seg,
                            interpret=True)
    assert (np.asarray(ref) == 0.0).all()
    assert (np.asarray(got) == 0.0).all()


@pytest.mark.parametrize("window", [0, 11])
def test_paged_segment_kernel_out_of_order_tables(rng, window):
    """Out-of-order physical blocks and -1 holes: only the table gives the
    store meaning; the scalar-prefetch gather must agree with the
    materialized-view oracle."""
    p, h, kv, d = 29, 4, 2, 16
    b, m, t = 3, 4, 8
    nb = b * m + 2                               # spare blocks stay unused
    q = jnp.asarray(rng.standard_normal((p, h, d)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((nb, kv, t, d)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((nb, kv, t, d)), jnp.float32)
    perm = rng.permutation(nb)[:b * m].astype(np.int32).reshape(b, m)
    perm[1, 3] = -1                              # unallocated hole
    perm[2, 2] = -1
    q_seg = jnp.asarray(rng.integers(-1, b, p), jnp.int32)
    q_pos = jnp.asarray(rng.integers(0, m * t, p), jnp.int32)
    tables = jnp.asarray(perm)
    ref = paged_segment_attention_ref(q, ks, vs, tables, q_pos, q_seg,
                                      window=window)
    got = paged_segment_attention(q, ks, vs, tables, q_pos, q_seg,
                                  window=window, block_q=8, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 2e-5, f"w={window}: {err:.2e}"


# Streams for the live walk: each case is (t, m, b, segments, p, block_q,
# window, holes).  A segment is (slot, first position, lanes), laid out in
# order from lane 0; the lanes after the last are dead.  Holes are
# (slot, table index) entries set to -1.
_WALK_CASES = {
    # a prefill chunk that starts mid-block, then three decode riders;
    # slot 0's range takes two chunks of 16 blocks
    "engine_stream": (16, 24, 4, [(2, 150, 20), (0, 300, 1), (1, 17, 1),
                                  (3, 260, 1)], 32, 16, 0, ()),
    "tile_straddles_two_segments": (8, 6, 2, [(0, 3, 5), (1, 10, 7)], 16, 8,
                                    0, ()),
    "segment_over_three_tiles": (8, 6, 2, [(1, 4, 20)], 24, 8, 0, ()),
    # slot 0's first live block is (90 - 12 + 1) // 8 = 9
    "window_first_block_above_0": (8, 16, 3, [(0, 90, 6), (2, 40, 1)], 8, 8,
                                   12, ()),
    "holes_in_live_range": (8, 8, 2, [(0, 50, 5), (1, 30, 3)], 16, 8, 0,
                            ((0, 1), (0, 4), (1, 2))),
    # dead tail lanes, and live lanes of slot 2 whose every block is
    # unallocated: both must come out as exact zeros
    "dead_lanes": (8, 4, 3, [(1, 10, 3), (2, 5, 2)], 16, 8, 0,
                   ((2, 0), (2, 1), (2, 2), (2, 3))),
    # T below the chunk: 8 blocks of 32 a chunk, slot 0 walks 8 + 8 + 3
    "t_below_chunk": (32, 20, 2, [(0, 600, 3), (1, 100, 2)], 8, 8, 0, ()),
    # T equal to the chunk: one 256-token block a step
    "t_equals_chunk": (256, 3, 2, [(0, 500, 4), (1, 700, 2)], 8, 8, 0, ()),
    # T not dividing 256 keys: 10 blocks of 24 a chunk, then 3
    "t_not_dividing_chunk_keys": (24, 13, 2, [(0, 290, 5), (1, 30, 1)], 8,
                                  8, 0, ()),
}


def _walk_case(rng, t, m, b, segments, p, holes, h=4, kv=2, d=16):
    nb = b * m + 3                               # spare blocks stay unused
    q = jnp.asarray(rng.standard_normal((p, h, d)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((nb, kv, t, d)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((nb, kv, t, d)), jnp.float32)
    tables = rng.permutation(nb)[:b * m].astype(np.int32).reshape(b, m)
    for slot, j in holes:
        tables[slot, j] = -1
    q_seg = np.full((p,), -1, np.int32)
    q_pos = np.zeros((p,), np.int32)
    cursor = 0
    for slot, first, n in segments:
        q_seg[cursor:cursor + n] = slot
        q_pos[cursor:cursor + n] = np.arange(first, first + n)
        cursor += n
    return q, ks, vs, jnp.asarray(tables), q_pos, q_seg


@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_paged_segment_kernel_walks_live_blocks(rng, case):
    """The paged kernel walks only each query tile's live block ranges, in
    chunks; on every stream shape it must agree with the oracle on every
    lane, with exact zeros where no key is visible."""
    t, m, b, segments, p, block_q, window, holes = _WALK_CASES[case]
    q, ks, vs, tables, q_pos, q_seg = _walk_case(rng, t, m, b, segments, p,
                                                 holes)
    ref = paged_segment_attention_ref(q, ks, vs, tables, jnp.asarray(q_pos),
                                      jnp.asarray(q_seg), window=window)
    got = paged_segment_attention(q, ks, vs, tables, jnp.asarray(q_pos),
                                  jnp.asarray(q_seg), window=window,
                                  block_q=block_q, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 2e-5, f"{case}: {err:.2e}"
    blind = (q_seg < 0) | np.isin(q_seg, [s for s, _ in holes
                                          if (np.asarray(tables)[s] < 0).all()])
    assert (np.asarray(ref)[blind] == 0.0).all()
    assert (np.asarray(got)[blind] == 0.0).all()
    if case == "t_not_dividing_chunk_keys":
        assert kv_chunk_blocks(t, 16, m) * t == 240


def _visible_blocks(q_pos, q_seg, b, m, t, window):
    """Brute force: per lane, the table indices holding a key the lane can
    see under the predicate (allocation aside)."""
    kp = np.arange(m * t)
    out = []
    for qp, qs in zip(q_pos, q_seg):
        ok = (qs >= 0) & (kp <= qp)
        if window > 0:
            ok &= (qp - kp) < window
        out.append(set(kp[ok] // t) if qs >= 0 else set())
    return out


@pytest.mark.parametrize("window", [0, 11])
def test_live_block_count_matches_kernel_table(rng, window):
    """The host's count (numpy) reads the same table the kernel's wrapper
    builds (jax) from interleaved, random lanes; every block a lane can see
    lies in its tile's range, and both ends of each range are seen."""
    p, b, m, t, block_q = 29, 3, 9, 8, 8
    table = jax.jit(functools.partial(
        live_block_ranges, num_slots=b, max_blocks=m, block_tokens=t,
        window=window, block_q=block_q))
    for _ in range(4):
        q_seg = rng.integers(-1, b, p).astype(np.int32)
        q_pos = rng.integers(0, m * t, p).astype(np.int32)
        host = live_block_ranges(q_pos, q_seg, num_slots=b, max_blocks=m,
                                 block_tokens=t, window=window,
                                 block_q=block_q)
        assert isinstance(host, np.ndarray)
        dev = np.asarray(table(jnp.asarray(q_pos), jnp.asarray(q_seg)))
        np.testing.assert_array_equal(host, dev)
        seen = _visible_blocks(q_pos, q_seg, b, m, t, window)
        for tile in range(host.shape[0]):
            lanes = range(tile * block_q, min(p, (tile + 1) * block_q))
            for slot in range(b):
                blocks = set().union(*(seen[i] for i in lanes
                                       if q_seg[i] == slot))
                first, last = host[tile, slot]
                if not blocks:
                    assert last < first
                    continue
                assert min(blocks) == first and max(blocks) == last
        assert live_blocks(host) == sum(
            max(0, int(hi) - int(lo) + 1) for lo, hi in host.reshape(-1, 2))


def test_engine_counts_attn_kv_blocks(rng):
    """``attn_kv_blocks`` is the kernel table's length for the stream the
    packed tick dispatched, averaged over the attention layers (gemma3's
    local and global layers walk different ranges); decode-only ticks
    count 0."""
    cfg = reduced(get_config("gemma3-4b"))
    params, _ = zoo.init(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=96,
                      enable_smartconf=False, prefill_mode="packed",
                      kv_mode="paged", block_tokens=8)
    eng.prefill_chunk = 16
    streams = []
    step = eng._step_unified

    def spy(*args):
        streams.append((np.asarray(args[3]), np.asarray(args[4])))
        return step(*args)

    eng._step_unified = spy
    for i, n in enumerate((40, 9)):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32), 6))
    windows = [cfg.window if cfg.block_pattern[i % len(cfg.block_pattern)]
               .split("+")[0] in ("swa", "local") else 0
               for i in range(cfg.num_layers)]
    assert len(set(windows)) == 2
    packed = decode_only = 0
    while len(eng.finished) < 2:
        before = len(streams)
        st = eng.tick()
        if len(streams) == before:
            decode_only += 1
            assert st["attn_kv_blocks"] == 0
            continue
        packed += 1
        slot_id, posw = streams[-1]
        want = np.mean([live_blocks(np.asarray(jax.jit(functools.partial(
            live_block_ranges, num_slots=2, max_blocks=eng.blocks_per_seq,
            block_tokens=8, window=w))(jnp.asarray(posw),
                                       jnp.asarray(slot_id))))
            for w in windows])
        assert st["attn_kv_blocks"] == pytest.approx(want)
        assert st["attn_kv_blocks"] > 0
    assert packed and decode_only
    eng.close()


def test_layers_segment_attention_zeroes_dead_lanes(rng):
    """The XLA twin in models.layers must zero dead pad lanes too (the
    bugfix satellite): uniform softmax over -1e30 scores previously emitted
    garbage on lanes no caller may read — which made exact XLA-vs-Pallas
    parity impossible."""
    p, n, h, d = 12, 24, 2, 8
    for dtype in (jnp.float32, jnp.bfloat16):
        q = jnp.asarray(rng.standard_normal((1, p, h, d)), dtype)
        k = jnp.asarray(rng.standard_normal((1, n, h, d)), dtype)
        v = jnp.asarray(rng.standard_normal((1, n, h, d)), dtype)
        q_seg = np.zeros((p,), np.int32)
        q_seg[7:] = -1                           # dead tail
        q_pos = np.arange(p, dtype=np.int32)
        k_seg = np.zeros((n,), np.int32)
        k_pos = np.arange(n, dtype=np.int32)
        out = layers.segment_attention(
            q, k, v, q_pos=jnp.asarray(q_pos)[None],
            k_pos=jnp.asarray(k_pos)[None], q_seg=jnp.asarray(q_seg)[None],
            k_seg=jnp.asarray(k_seg)[None])
        assert (np.asarray(out, np.float32)[0, 7:] == 0.0).all(), dtype


def test_segment_op_env_dispatch(rng, monkeypatch):
    """REPRO_SEGMENT_IMPL routes the op between the oracle and the
    interpreted kernel; both agree on live lanes."""
    p, n, h, d = 16, 32, 2, 8
    q = jnp.asarray(rng.standard_normal((p, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
    q_pos, q_seg, k_pos, k_seg = _ragged_stream(rng, p, n, 2)
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setenv("REPRO_SEGMENT_IMPL", impl)
        outs[impl] = segment_attention_op(q, k, v, q_pos, k_pos, q_seg,
                                          k_seg)
    err = float(jnp.max(jnp.abs(outs["xla"] - outs["pallas_interpret"])))
    assert err < 2e-5
    monkeypatch.setenv("REPRO_SEGMENT_IMPL", "bogus")
    with pytest.raises(ValueError, match="kernel impl"):
        segment_attention_op(q, k, v, q_pos, k_pos, q_seg, k_seg)
