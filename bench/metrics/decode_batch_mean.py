"""Mean number of requests decoding in a tick, over the window's ticks that
decode (the engine's ``decode_slots`` counter)."""


def read(run):
    slots = [r.stats["decode_slots"] for r in run.records
             if r.stats["decode_slots"]]
    return sum(slots) / len(slots) if slots else None
