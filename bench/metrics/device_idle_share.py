"""Device: share of the traced window in which no operation ran on the
device (1 - union of op intervals / window, averaged over the chips), in
%; moves ``tpot_p95_ms``.  Nothing to read without a trace."""


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
