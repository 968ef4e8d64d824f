"""95th percentile of the gaps between consecutive tokens of one request,
over every gap whose later token was emitted in the window (host clock; a
token counts as emitted when the tick that sampled it returns)."""

from bench import loadgen


def read(run):
    p = loadgen.percentile(run.gaps, 95.0)
    return None if p is None else p * 1e3
