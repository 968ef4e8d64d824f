"""Roofline share of the paged segment-attention kernel: the least time the
attention work of the live segments of the window's packed ticks needs on
the chip (``bench/work.py`` at ``bench/peaks.py``, per layer the larger of
FLOPs over peak and bytes over bandwidth) over the kernel's device time in
the trace.  ``run.notes`` records which bound held."""

from bench import work


def read(run):
    if run.reduction is None or run.peaks is None:
        return None
    seconds, calls = run.reduction.kernel("segment_attention_paged")
    if not calls or seconds <= 0:
        return None
    need, bounds = 0.0, {"compute": 0.0, "memory": 0.0}
    for r in run.records:
        if r.width is None or not r.segments:
            continue
        t, bound = work.attention_min_seconds(
            run.shape, r.segments, run.peaks.bf16_flops,
            run.peaks.hbm_bytes_per_s)
        need += run.shape.layers * t
        bounds[bound] += run.shape.layers * t
    if need <= 0:
        return None
    run.notes["segment_attn_roofline"] = (
        f"{max(bounds, key=bounds.get)}-bound for "
        f"{100.0 * max(bounds.values()) / need:.1f}% of the least time")
    return 100.0 * need / seconds
