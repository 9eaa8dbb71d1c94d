"""95th percentile of every gap between consecutive tokens a client
received, over all requests, for gaps ending in the window."""
from bench import stats


def read(rec):
    p = stats.percentile(stats.token_gaps(rec["timeline"], *rec["window"]), 95)
    return None if p is None else p * 1e3
