"""The work a tick needs, from shapes alone: the FLOPs and bytes of the live
segments it carries, not of what the compiled program pads or masks.
Attention is counted here for every family, since each has ordinary
attention layers; the rest of a token's matmuls is the family's own count,
``shape.matmul_flops_per_token()`` (``bench/families/<family>.py``).

A segment is ``(start, n)``: ``n`` tokens of one request at positions
``start .. start + n - 1``, each attending causally to every earlier
position of its own request and to itself.  A decode rider is ``(pos, 1)``.
Bytes are counted at 2 per element (bf16 operands)."""

from __future__ import annotations

ELEM = 2


def _keys(start: int, n: int) -> int:
    """Query-key pairs of a causal segment: sum over i < n of start + i + 1."""
    return n * start + n * (n + 1) // 2


def attention_flops(s, segments) -> int:
    """QK^T and PV of one layer over the live segments."""
    return 4 * s.heads * s.head_dim * sum(_keys(a, n) for a, n in segments)


def attention_bytes(s, segments) -> int:
    """Least bytes one layer's attention kernel must move: each query read
    and each output written once, and each segment's K and V context
    (``start + n`` positions) read once."""
    q_and_o = 2 * s.heads * s.head_dim * sum(n for _, n in segments)
    kv = 2 * s.kv_heads * s.head_dim * sum(a + n for a, n in segments)
    return ELEM * (q_and_o + kv)


def head_flops(s, sampled_rows: int) -> int:
    return 2 * s.d_model * s.vocab * sampled_rows


def step_flops(s, segments, sampled_rows: int) -> int:
    """Everything one tick needs: matmuls for its live tokens, attention
    over live context in every layer, the head at the rows it samples."""
    tokens = sum(n for _, n in segments)
    return (s.matmul_flops_per_token() * tokens
            + s.layers * attention_flops(s, segments)
            + head_flops(s, sampled_rows))


def attention_min_seconds(s, segments, peak_flops: float,
                          peak_bytes_per_s: float) -> tuple[float, str]:
    """Least time one layer's attention call can take on the chip, and the
    bound that sets it (``"compute"`` or ``"memory"``)."""
    tf = attention_flops(s, segments) / peak_flops
    tb = attention_bytes(s, segments) / peak_bytes_per_s
    return (tf, "compute") if tf >= tb else (tb, "memory")
