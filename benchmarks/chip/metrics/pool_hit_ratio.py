"""Block lookups of the KV pool that hit over all lookups, from the
pool's ``pool_lookups_total`` counters over the window."""


def read(run):
    n = run.pool_delta["hit"] + run.pool_delta["miss"]
    return run.pool_delta["hit"] / n if n else None
