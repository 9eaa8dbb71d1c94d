"""Engine layer: one admission's service time, from prefill dispatch to
the first token on the host (the engine's ``stats["prefill_s"]`` over
``stats["prefills"]``) over the window, in ms.  Moves ``ttft_p50_ms``.
Nothing to read from an engine without the counter."""


def read(rec):
    s0, s1 = rec["stats"]
    if "prefills" not in s1:
        return None
    n = s1["prefills"] - s0["prefills"]
    return None if n <= 0 else (s1["prefill_s"] - s0["prefill_s"]) / n * 1e3
