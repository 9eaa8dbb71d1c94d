"""Engine layer: host time of one ``ContinuousBatchingEngine.step()``
(the engine's own ``stats["step_s"]`` over ``stats["steps"]``), over the
window; moves ``tpot_p95_ms``."""


def read(rec):
    s0, s1 = rec["stats"]
    steps = s1["steps"] - s0["steps"]
    return None if steps <= 0 else (s1["step_s"] - s0["step_s"]) / steps * 1e3
