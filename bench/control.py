"""The readings ``max_logit_gap``'s limit is set from: the program's
widest gap over many seeds, and the int4 control's, on the cell's own
traffic at its own sizes.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

One process serves every seed (one engine, its weights swapped per seed, so
the programs compile once), then frees it and runs the reference with its
int4 control over each seed's sample, both through the harness's own
comparison (``harness.check``, the configuration's limit, the seed's count
of failed requests).  Prints one JSON line per seed: ``program`` is the
widest gap of a served token below the reference's best and
``program_correct`` its verdict; ``control`` is the widest gap of the token
the int4 control puts first, at the same positions, and ``control_correct``
its verdict (false, where the comparison separates them).  Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, seeds: list[int], seconds: float, devices) -> list[dict]:
    from bench import harness as H
    from bench import stats as S
    from bench import weights as W
    from bench.traffic import generators as G
    from repro.serve.quantize import quantize_tree

    serving, m = cell.mix["serving"], cell.config["model"]
    cfg = H.model_config(cell.config)
    eng, samples = None, []
    for i, seed in enumerate(seeds):
        wseed = W.derive_seed(seed, H.SEED_WEIGHTS)
        traffic = G.Traffic(cell.mix, W.derive_seed(seed, H.SEED_TRAFFIC),
                            m["vocab_size"])
        if eng is not None:
            eng.params = eng.qparams = None
            gc.collect()
        params = H.build_params(cfg, cell.config, wseed)
        if eng is None:
            eng = H.traced_engine(cfg, params, serving)
        else:
            eng.params, eng.qparams = params, quantize_tree(params)
        del params
        out = H.serve(eng, traffic, serving, seconds, False,
                      W.derive_seed(seed, H.SEED_WARM) if i == 0 else None)
        timeline = [s.record() for s in out["sent"]]
        n_failed = sum(S.failed(r) for r in S.due_in(timeline, *out["window"]))
        sample = H.sample_for_check(timeline, out["sent"], out["window"],
                                    W.derive_seed(seed, H.SEED_SAMPLE))
        samples.append((seed, wseed, n_failed, sample))
    del eng
    gc.collect()
    rows = []
    for seed, wseed, n_failed, sample in samples:
        row = {"seed": seed, "failed": n_failed}
        for side in ("program", "control"):
            ok, checks = H.check(cell.config, wseed, sample,
                                 serving["max_len"], n_failed,
                                 control=side == "control")
            row["tokens"] = checks["checked_tokens"]["value"]
            row[side] = checks.get("max_logit_gap", {}).get("value")
            row[f"{side}_correct"] = ok
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness as H
    from repro.launch.compile_cache import enable_compile_cache

    cell = H.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    for row in readings(cell, [int(s) for s in args.seeds.split(",")],
                        args.seconds, devices[:cell.chips]):
        print(json.dumps({"workload": cell.name, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
