"""The whole step's share of the chip's bf16 peak: the FLOPs the window's
live tokens need (matmuls of every live token, attention over live context,
the head at the rows sampled; ``bench/work.py``) over the window's seconds
times the peak."""

from bench import work


def read(run):
    if run.peaks is None or not run.records:
        return None
    flops = sum(work.step_flops(run.shape, r.segments, r.sampled)
                for r in run.records)
    span = run.records[-1].t1 - run.records[0].t0
    if flops == 0 or span <= 0:
        return None
    return 100.0 * flops / (span * run.peaks.bf16_flops)
