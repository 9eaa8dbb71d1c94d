"""90th percentile, over every request due in the window, of its first
token's arrival minus its due time; a request that failed counts as never
served (+inf)."""
from bench import stats


def read(rec):
    p = stats.percentile(stats.ttfts(rec["timeline"], *rec["window"]), 90)
    return None if p is None else p * 1e3
