"""Model step of a MoE share, whole-window utilisation: bytes the window's
decode steps must read (each step's weights once, routed experts only
where the step touched them: the engine's ``stats["moe_experts_touched"]``,
plus each generated token's latent rows in context), over the whole window
(idle time included) times the chip's HBM bandwidth, in %; moves
``tokens_per_s``.  Nothing to read from an engine without the counter."""
from bench import model_cost


def read(rec):
    m, (t0, t1) = rec["model"], rec["window"]
    s0, s1 = rec["stats"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    if "moe_experts_touched" not in s1 or steps <= 0:
        return None
    C = model_cost.counts(rec["family_module"])
    touched = s1["moe_experts_touched"] - s0["moe_experts_touched"]
    kv = sum(C.latent_bytes(m, r["prompt_len"] + j)
             for r in rec["timeline"] for j, t in enumerate(r["times"])
             if j > 0 and t0 <= t < t1)
    total = (steps * C.decode_fixed_bytes(m) + touched * C.expert_bytes(m)
             + kv)
    bw = model_cost.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * total / ((t1 - t0) * bw * rec["chips"])
