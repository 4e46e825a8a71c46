"""A whole run, but for the look for a chip, at a small size on the CPU:
sound, it reads `correct`; with the timed path broken beneath the front
door it reads not correct.  The faults a search cell can have: an answer
altered where it is produced, and half of a batch left unanswered."""
import copy
import time

import numpy as np
import pytest

from bench.lib import harness, spec

CELL = "paper45g.rare-bulk"


def _run(fault):
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    cfg = copy.deepcopy(spec.config(cell["config"]))
    cfg["corpus"].update(n_docs=6, median_doc_len=3000, max_doc_len=8000)
    mix = copy.deepcopy(spec.mix(cell["traffic"]))
    mix["pool"] = 16
    mix["front"]["max_batch"] = 4
    mix["arrivals"]["in_flight"] = 8
    result, _ = harness.run_cell(bench, cell, cfg, mix, 21, 2, False,
                                 time.monotonic(), require_tpu=False,
                                 fault=fault, cache_index=False,
                                 peaks={"hbm_bytes_per_s": 1e11})
    return result


def altered(requests, out):
    for r in out:
        if len(r.pos):
            r.pos = r.pos + np.int32(1)
    return out


def half_left_out(requests, out):
    for r in out[len(out) // 2:]:
        r.doc, r.pos = r.doc[:0], r.pos[:0]
        r.doc_only = False
        r.subplan_pos_hits = tuple(0 for _ in r.subplan_pos_hits)
    return out


def test_sound_run_is_correct():
    r = _run(None)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 16 and r["failed"] == 0
    assert r["compared"]["answers_checked"]["value"] == r["attempted"]
    assert set(r["metrics"]) == {"setup_s", "throughput_qps"}
    assert r["metrics"]["throughput_qps"]["value"] > 0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("fault", [altered, half_left_out])
def test_broken_path_is_not_correct(fault):
    r = _run(fault)
    assert not r["correct"]
    assert r["compared"]["answers_wrong"]["value"] > 0
