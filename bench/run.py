#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is read from BENCHMARK.json
at the checkout's root; its files are found by name under bench/.  With
--trace 0 the result reports the cell's end-to-end metrics, with --trace 1
its per-layer metrics, read from a profiler trace of the window.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, (breakdown, with --trace 1), and last the numbers
compared against the plain reference, each with its limit; those numbers
are also the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _die(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        _die("--seed must be >= 0")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import repro  # noqa: F401
    except ImportError as e:
        _die(f"the program is not in this checkout: {e}")
    from bench.lib import harness, spec
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    try:
        result, _ = harness.run_cell(
            bench, cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
            T_START, trace_dir=ROOT / "bench" / ".trace")
    except harness.NoChip as e:
        _die(str(e))
    from bench.lib import check
    for name, v in result["compared"].items():
        print(check.describe(name, v), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
