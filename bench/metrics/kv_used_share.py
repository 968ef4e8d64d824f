"""Mean share of the KV blocks the engine may hand out that are in use,
over the window's ticks.  The blocks it may hand out are the lesser of the
KV budget and the block store's capacity (the budget of the HBM controller
can stand above the store)."""


def read(run):
    shares = [r.stats["kv_used_blocks"]
              / min(r.stats["kv_budget_blocks"], r.stats["kv_capacity_blocks"])
              for r in run.records
              if min(r.stats["kv_budget_blocks"], r.stats["kv_capacity_blocks"])]
    return 100.0 * sum(shares) / len(shares) if shares else None
