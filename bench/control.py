#!/usr/bin/env python3
"""The control of a cell's check: the plain reference put in the program's
place with in-document positions held in int16 (the configuration states
int32), compared with the reference as a run compares the program.

    python bench/control.py --workload <cell> --seeds 11 12 13 --requests 8000

For each seed it takes the requests a run of that seed sends first (the
seed's order over the pool; `--requests` of them, about what one run
attempts) and prints one JSON line with the numbers `bench/run.py`
compares.  A sound limit lies below what the control reads; PERF.md gives
the readings.  Host numpy only: it touches no accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class World:
    """A cell's data, pool, reference and control, drawn once."""

    def __init__(self, cfg: dict, mix: dict):
        from bench.lib import corpus as bcorpus, traffic
        from bench.lib.reference import Reference
        lex = bcorpus.lexicon_from(cfg, cfg["data_seed"])
        forms = bcorpus.draw_forms(lex)
        corp = bcorpus.corpus_from(cfg, lex, forms, cfg["data_seed"])
        self.pool = traffic.make_pool(mix, corp, lex, forms, cfg["data_seed"])
        self.ref = Reference(corp, lex, forms, cfg["index"])
        self.control = Reference(corp, lex, forms, cfg["index"],
                                 pos_dtype=np.int16)
        self._wrong: dict = {}

    def wrong(self, i: int) -> bool:
        """Whether the control's answer to pool entry i is wrong."""
        from bench.lib import check
        if i not in self._wrong:
            q = self.pool[i]
            got = check.answer_fields(self.control.answer(q))
            self._wrong[i] = check.compare(got, self.ref.answer(q)) is not None
        return self._wrong[i]

    def readings(self, seed: int, n_requests: int) -> dict:
        from bench.lib import check, traffic
        order = traffic.pool_order(len(self.pool), n_requests, seed)
        wrong = sum(self.wrong(int(i)) for i in order)
        return {k: v["value"]
                for k, v in check.judge(wrong, 0, len(order)).items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from bench.lib import spec
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    t0 = time.monotonic()
    world = World(spec.config(cell["config"]), spec.mix(cell["traffic"]))
    for seed in args.seeds:
        r = world.readings(seed, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "int16_positions": r,
                          "seconds": time.monotonic() - t0}), flush=True)


if __name__ == "__main__":
    main()
