"""Record a short traced window of a cell and keep its first slice as a
test fixture for the trace reduction.

    python bench/record_trace.py --workload <cell> --seed 5 --seconds 4 \
        --keep 0.25 --out bench/testdata/<name>.json.gz

Writes the trimmed trace and, beside it (``.expected.json``), what
:func:`bench.trace.reduce` makes of it, so the test can reproduce both.
Prints the trace's planes and lines with their event counts.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--keep", type=float, default=0.25,
                    help="seconds of the window to keep")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness as H
    from bench import trace as TR
    from bench import weights as W
    from bench.traffic import generators as G
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = H.load_cell(args.workload)
    serving, m = cell.mix["serving"], cell.config["model"]
    cfg = H.model_config(cell.config)
    params = H.build_params(cfg, cell.config,
                           W.derive_seed(args.seed, H.SEED_WEIGHTS))
    eng = H.traced_engine(cfg, params, serving)
    del params
    traffic = G.Traffic(cell.mix, W.derive_seed(args.seed, H.SEED_TRAFFIC),
                        m["vocab_size"])
    H.serve(eng, traffic, serving, args.seconds, True,
            W.derive_seed(args.seed, H.SEED_WARM))
    path = TR.find_xplane(H.TRACE_DIR)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": len(evs),
                              "first": [[e.name, e.start_ns, e.duration_ns]
                                        for e in evs[:3]]}))
    full = TR.load_xplane(path)
    print(json.dumps({"full_reduction": TR.reduce(full)}))
    kept = TR.trim(full, args.keep)
    out = Path(args.out)
    TR.save(kept, out)
    out.with_suffix("").with_suffix(".expected.json").write_text(
        json.dumps(TR.reduce(kept), indent=1) + "\n")
    print(f"kept {args.keep} s: {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
