"""Model step, whole-window utilisation: bytes the window's decode steps
must read (each step's int8 weights, head and norms once, plus each
generated token's K/V rows in context), over the whole window (idle time
included) times the chip's HBM bandwidth, in %; moves ``tokens_per_s``."""
from bench import model_cost


def read(rec):
    m, (t0, t1) = rec["model"], rec["window"]
    C = model_cost.counts(rec["family_module"])
    s0, s1 = rec["stats"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    kv = sum(C.kv_bytes(m, r["prompt_len"] + j)
             for r in rec["timeline"] for j, t in enumerate(r["times"])
             if j > 0 and t0 <= t < t1)
    if steps <= 0:
        return None
    total = steps * C.decode_weight_bytes(m) + kv
    bw = model_cost.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * total / ((t1 - t0) * bw * rec["chips"])
