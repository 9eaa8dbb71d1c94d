"""Engine layer: the engine's own host work per step, serialised with the
device (``stats["step_s"]`` less the time blocked on device results,
``stats["wait_s"]``, over ``stats["steps"]``) over the window, in ms:
scheduling, pushes, dispatch, sampling and bookkeeping.  Moves
``tpot_p95_ms``.  Nothing to read from an engine without the counter."""


def read(rec):
    s0, s1 = rec["stats"]
    steps = s1["steps"] - s0["steps"]
    if "wait_s" not in s1 or steps <= 0:
        return None
    host = (s1["step_s"] - s0["step_s"]) - (s1["wait_s"] - s0["wait_s"])
    return host / steps * 1e3
