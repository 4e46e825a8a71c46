"""The control fails the check: the reference put in the program's place
with in-document positions held in int16 reads wrong answers where
documents pass 32,767 tokens, and none where they do not.  (On the chip the
control runs at the cell's own size: `bench/control.py`.)"""
import importlib.util

import pytest

from bench.lib import spec


def _world(n_docs, median_doc_len, max_doc_len):
    s = importlib.util.spec_from_file_location(
        "bench_control", spec.BENCH / "control.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    cfg = spec.config("paper45g-1of512")
    cfg["corpus"].update(n_docs=n_docs, median_doc_len=median_doc_len,
                         max_doc_len=max_doc_len)
    mix = spec.mix("rare-bulk")
    mix["pool"] = 32
    return mod.World(cfg, mix)


@pytest.fixture(scope="module")
def long_docs():
    return _world(4, 60000, 70000)


@pytest.fixture(scope="module")
def short_docs():
    return _world(8, 3000, 8000)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int16_positions_fail_on_long_documents(long_docs, seed):
    r = long_docs.readings(seed, 80)
    assert r["answers_checked"] == 80
    assert r["answers_wrong"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int16_positions_pass_on_short_documents(short_docs, seed):
    r = short_docs.readings(seed, 80)
    assert r["answers_wrong"] == 0 and r["answers_missing"] == 0
