"""Output tokens clients received in the window, over the window."""
from bench import stats


def read(rec):
    t0, t1 = rec["window"]
    return stats.tokens_in(rec["timeline"], t0, t1) / (t1 - t0)
