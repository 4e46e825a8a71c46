"""BENCHMARK.json against the benchmark's contract, and the discovery of
every configuration, mix and metric by name."""
import re

import pytest

from bench.lib import spec

BENCH = spec.benchmark()
ALLOWED = {"command", "paths", "run_seconds", "configs", "workloads",
           "end_to_end", "per_layer"}


def test_keys_and_names():
    assert set(BENCH) == ALLOWED
    assert spec.validate(BENCH) == []
    assert 1 <= BENCH["run_seconds"] <= 51


def test_command_and_paths():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and 1 <= len(paths) <= 16
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (spec.CHECKOUT / p).is_dir()
    for word in cmd:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths), word


def test_entries_have_only_their_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, e["name"]


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    w = spec.cell(BENCH, cell)
    cfg = spec.config(w["config"])
    mix = spec.mix(w["traffic"])
    assert cfg["name"] == w["config"]
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (spec.CHECKOUT / entry["file"]).is_file()
    assert entry["reduced"] == list(cfg["reduced"])
    assert w["chips"] in (1, 4)
    assert mix["arrivals"]["loop"] == "closed"
    e2e = spec.metrics_for(BENCH, cell, trace=False)
    layer = spec.metrics_for(BENCH, cell, trace=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert layer
    moved = {m["name"] for m in e2e}
    for m in e2e + layer:
        assert callable(spec.reader(m["name"]))
    for m in layer:
        assert m["moves"] in moved


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    assert len(layers) <= len(BENCH["per_layer"])


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
