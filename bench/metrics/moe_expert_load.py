"""MoE layer: routed pairs per held expert per MoE layer per decode step
over the window (the engine's ``stats["moe_local_pairs"]`` over
``stats["decode_steps"]``, the configuration's MoE layers and its held
experts).  Every slot of the pool routes, so a slot that holds no request
counts too.  Moves ``tokens_per_s``.  Nothing to read from an engine
without the counter."""
from bench import model_cost


def read(rec):
    s0, s1 = rec["stats"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    if "moe_local_pairs" not in s1 or steps <= 0:
        return None
    C = model_cost.counts(rec["family_module"])
    m = rec["model"]
    pairs = s1["moe_local_pairs"] - s0["moe_local_pairs"]
    return pairs / (steps * C.moe_layers(m) * C.held(m))
