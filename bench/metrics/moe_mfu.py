"""Model step of a MoE share, whole-window utilisation: FLOPs the
algorithm needs for the prompts prefilled and the tokens generated in the
window (a request's first token stands for its prefill), routed experts
counted per pair that landed on a held expert (the engine's
``stats["moe_local_pairs"]``, every slot of the decode batch, and
``stats["moe_prefill_local_pairs"]``, the prompt rows without padding),
over the whole window (idle time included) times the chip's bf16 peak,
in %; moves ``tokens_per_s``.  Nothing to read from an engine without the
counters."""
from bench import model_cost


def read(rec):
    m, (t0, t1) = rec["model"], rec["window"]
    s0, s1 = rec["stats"]
    if "moe_local_pairs" not in s1:
        return None
    C = model_cost.counts(rec["family_module"])
    flops = 0
    for r in rec["timeline"]:
        for j, t in enumerate(r["times"]):
            if t0 <= t < t1:
                flops += (C.prefill_flops(m, r["prompt_len"]) if j == 0
                          else C.decode_flops(m, r["prompt_len"] + j))
    pairs = sum(s1[k] - s0[k] for k in ("moe_local_pairs",
                                        "moe_prefill_local_pairs"))
    flops += pairs * C.expert_pair_flops(m)
    if flops == 0:
        return None
    peak = model_cost.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / ((t1 - t0) * peak * rec["chips"])
