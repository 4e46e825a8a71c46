"""`BENCHMARK.json` and the files it names, found by name.

* a configuration: `bench/configs/<config>.json`
* a traffic mix: `bench/traffic/<traffic>.json`
* a metric (end-to-end or per-layer): `bench/metrics/<name>.py`, a module
  with `read(ctx) -> float | None` (None: nothing to read in this run)
* device peaks: `bench/peaks.json`, keyed by JAX's `device_kind`
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = CHECKOUT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in bench/peaks.json")
    return table[kind]


def metrics_for(spec: dict, cell_name: str, trace: bool) -> list:
    """The metric entries a run of `cell_name` reports: its end-to-end
    metrics (trace off), or its per-layer metrics (trace on)."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str):
    """The `read` function of bench/metrics/<name>.py."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def validate(spec: dict) -> list:
    """Breaches of the name and unit rules, as messages (empty: none)."""
    bad = []
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec[key]:
            names.append((key, e["name"]))
            if not NAME.match(e["name"]):
                bad.append(f"{key}: bad name {e['name']!r}")
            if "unit" in e and not UNIT.match(e["unit"]):
                bad.append(f"{key}: bad unit {e['unit']!r} of {e['name']}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                bad.append(f"{key}: bad better of {e['name']}")
    for w in spec["workloads"]:
        for k in ("config", "traffic"):
            if not NAME.match(w[k]):
                bad.append(f"workload {w['name']}: bad {k} {w[k]!r}")
    for c in spec["configs"]:
        bad += [f"config {c['name']}: bad reduced key {k!r}"
                for k in c["reduced"] if not NAME.match(k)]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [n for k, n in names if k == key]
        if len(seen) != len(set(seen)):
            bad.append(f"{key}: duplicate names")
    metric_names = [n for k, n in names if k in ("end_to_end", "per_layer")]
    if len(metric_names) != len(set(metric_names)):
        bad.append("a metric name is used twice")
    return bad
