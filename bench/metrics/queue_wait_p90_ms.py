"""Server layer: 90th percentile, over every request due in the window, of
its due time to its admission into an engine slot (``Request.admit_time``,
on the same clock); one never admitted is +inf.  Moves ``ttft_p90_ms``."""
from bench import stats


def read(rec):
    p = stats.percentile(stats.queue_waits(rec["timeline"], *rec["window"]),
                         90)
    return None if p is None else p * 1e3
