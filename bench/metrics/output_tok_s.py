"""Output tokens per second: every token emitted by the window's ticks,
over the time from the first such tick's start to the last one's end
(host clock, the device waited for at the close)."""

from bench import loadgen


def read(run):
    return loadgen.output_tok_s(run.records)
