"""Share of the packed stream's prefill lanes that carried no prompt token,
over the window's ticks (the engine's tick counters)."""


def read(run):
    issued = sum(r.stats["prefill_issued_tokens"] for r in run.records)
    live = sum(r.stats["prefill_tokens"] for r in run.records)
    return 100.0 * (issued - live) / issued if issued else None
