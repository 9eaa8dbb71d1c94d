"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from the seed on the device, warms the shapes its
traffic uses, serves that traffic through ``AsyncServer`` for ``--seconds``,
checks the served tokens against the float32 reference, and prints one JSON
result line last on stdout (with ``--trace 1``: the per-layer metrics from a
profiler trace of the window).  The numbers compared, with their limits,
close both the result line (``checks``) and stderr.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or outside a checkout that holds the program.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        import repro
        where = [Path(p) for p in repro.__path__]
    except ImportError:
        where = []
    if not any(p.is_relative_to(ROOT / "src") for p in where):
        print(f"bench: the program is not in this checkout ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              devices[:cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(harness.finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
