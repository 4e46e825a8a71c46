"""The on-disk index cache: what it loads equals what was built, a
configuration keeps one file, and any change to the configuration gives
another key."""
import copy
import pickle

import numpy as np
import pytest

from bench.lib import harness, index_cache, spec


@pytest.fixture(scope="module")
def small():
    cfg = copy.deepcopy(spec.config("paper45g-1of512"))
    cfg["corpus"].update(n_docs=3, median_doc_len=2000, max_doc_len=5000)
    return cfg, harness.build_world(cfg, cache=False)


def test_round_trip_is_exact(small, tmp_path, monkeypatch):
    monkeypatch.setattr(index_cache, "CHUNK", 1 << 16)  # many frames
    _cfg, (_lex, _forms, _corp, index) = small
    path = tmp_path / "x.zidx"
    size = index_cache.save(index, path)
    assert path.stat().st_size == size and not path.with_suffix(".tmp").exists()
    back = index_cache.load(path)
    assert pickle.dumps(back, protocol=5) == pickle.dumps(index, protocol=5)
    arr = back.ordinary.offsets
    assert isinstance(arr, np.ndarray) and arr.flags.writeable


def test_load_or_build_builds_once(small, tmp_path, monkeypatch):
    monkeypatch.setattr(index_cache, "CACHE", tmp_path)
    cfg, (_lex, _forms, _corp, index) = small
    built = []

    def build():
        built.append(1)
        return index

    _, how = index_cache.load_or_build(cfg, build, lambda m: None)
    _, how2 = index_cache.load_or_build(cfg, build, lambda m: None)
    assert (how, how2, len(built)) == ("built", "loaded", 1)
    other = copy.deepcopy(cfg)
    other["index"]["near_window"] += 1
    index_cache.load_or_build(other, build, lambda m: None)
    assert len(built) == 2
    assert len(list((tmp_path / cfg["name"]).glob("*.zidx"))) == 1


@pytest.mark.parametrize("part,key", [("corpus", "n_docs"),
                                      ("lexicon", "n_stop"),
                                      ("index", "max_distance")])
def test_key_follows_the_configuration(small, part, key):
    cfg = small[0]
    other = copy.deepcopy(cfg)
    other[part][key] += 1
    assert index_cache.key(other) != index_cache.key(cfg)
    assert index_cache.key(copy.deepcopy(cfg)) == index_cache.key(cfg)


def test_a_foreign_file_is_refused(tmp_path):
    p = tmp_path / "bad.zidx"
    p.write_bytes(b"not an index")
    with pytest.raises(ValueError):
        index_cache.load(p)
