"""Host time per tick: the mean, over the window's ticks, of each tick's
``serve.tick`` span less its ``serve.wait`` and ``serve.fetch`` spans, the
time the engine's thread works on the tick without waiting on the chip.
The engine times those spans on its own clock and reports the difference
as the tick counter ``host_s``; a program without that counter reads
nothing."""


def read(run):
    host = [r.stats["host_s"] for r in run.records if "host_s" in r.stats]
    return 1e3 * sum(host) / len(host) if host else None
