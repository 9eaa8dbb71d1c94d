"""Find an open-loop mix's knee: the highest Poisson rate the served path
sustains without a growing backlog, on this machine's chip.

    python bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 30

One engine serves every rate in turn (warmed once).  Per rate it prints the
completed request rate, the TTFT and token-gap tails, and the queue wait
in the first and last third of the window: a backlog that grows shows as a
last-third wait far above the first.  The cell's traffic file then fixes
its rate as a number (0.8 of the knee); a run never searches.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def sweep(cell, rates: list[float], seconds: float, seed: int):
    from bench import harness as H
    from bench import stats as S
    from bench import weights as W
    from bench.traffic import generators as G

    serving, m = cell.mix["serving"], cell.config["model"]
    cfg = H.model_config(cell.config)
    params = H.build_params(cfg, cell.config,
                           W.derive_seed(seed, H.SEED_WEIGHTS))
    eng = H.traced_engine(cfg, params, serving)
    del params
    for i, rate in enumerate(rates):
        mix = dict(cell.mix, rate=rate)
        traffic = G.Traffic(mix, W.derive_seed(seed, H.SEED_TRAFFIC),
                            m["vocab_size"])
        out = H.serve(eng, traffic, serving, seconds, False,
                      W.derive_seed(seed, H.SEED_WARM) if i == 0 else None)
        tl = [s.record() for s in out["sent"]]
        t0, t1 = out["window"]
        third = (t1 - t0) / 3
        waits = lambda a, b: S.percentile(
            [w for w in S.queue_waits(tl, a, b)], 50)
        done = [r for r in tl if r["times"] and r["times"][-1] < t1]
        yield {"rate": rate, "due": len(S.due_in(tl, t0, t1)),
               "completed_per_s": len(done) / seconds,
               "failed": sum(S.failed(r) for r in tl),
               **{f"ttft_p{q}_ms": S.percentile(S.ttfts(tl, t0, t1), q) * 1e3
                  for q in (50, 90, 95)},
               **{f"tpot_p{q}_ms":
                  S.percentile(S.token_gaps(tl, t0, t1), q) * 1e3
                  for q in (50, 90, 95, 99)},
               "tokens_per_s": S.tokens_in(tl, t0, t1) / seconds,
               "queue_p50_first_third_ms": waits(t0, t0 + third) * 1e3,
               "queue_p50_last_third_ms": waits(t1 - third, t1) * 1e3}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness as H
    from repro.launch.compile_cache import enable_compile_cache

    cell = H.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from bench.traffic import generators as G
    if "rate" not in cell.mix or G.loop(cell.mix["loop"]).gaps(
            cell.mix, 1) is None:
        print("sweep: the cell's loop has no rate", file=sys.stderr)
        return 2
    enable_compile_cache()
    for row in sweep(cell, [float(r) for r in args.rates.split(",")],
                     args.seconds, args.seed):
        print(json.dumps({"workload": cell.name, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
