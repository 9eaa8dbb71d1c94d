"""Engine layer: host time blocked on each decode step's result (the
engine's ``stats["decode_wait_s"]`` over ``stats["decode_steps"]``) over
the window, in ms: device compute still running plus the return of the
tokens.  Moves ``tpot_p95_ms``.  Nothing to read from an engine without
the counter."""


def read(rec):
    s0, s1 = rec["stats"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    if "decode_wait_s" not in s1 or steps <= 0:
        return None
    return (s1["decode_wait_s"] - s0["decode_wait_s"]) / steps * 1e3
