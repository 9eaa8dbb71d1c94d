"""Model step, whole-window utilisation: FLOPs the algorithm needs for the
prompts prefilled and the tokens generated in the window (a request's
first token stands for its prefill), over the whole window (idle time
included) times the chip's bf16 peak, in %; moves ``tokens_per_s``."""
from bench import model_cost


def read(rec):
    m, (t0, t1) = rec["model"], rec["window"]
    C = model_cost.counts(rec["family_module"])
    flops = 0
    for r in rec["timeline"]:
        for j, t in enumerate(r["times"]):
            if t0 <= t < t1:
                flops += (C.prefill_flops(m, r["prompt_len"]) if j == 0
                          else C.decode_flops(m, r["prompt_len"] + j))
    if flops == 0:
        return None
    peak = model_cost.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / ((t1 - t0) * peak * rec["chips"])
