"""Reduction of a profiler trace to the benchmark's device numbers.

`extract(xplane_path)` reads the JAX profiler's `.xplane.pb` with JAX alone
and keeps what the metrics need, as plain lists on one clock (ns from the
trace's start): the device's operations, per chip, and the host spans the
benchmark itself wrote (`jax.profiler.TraceAnnotation`).  Everything else
here works on those lists, so a test can feed it a small recorded trace.
"""
from __future__ import annotations

import glob
import os

SPAN_CALL = "bench.backend_call"
SPAN_WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def extract(path: str) -> dict:
    """{"devices": {plane: [(name, start, end), ...]},
        "spans": {name: [(start, end), ...]}, "planes": {plane: [lines]}}"""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans, planes = {}, {}, {}
    for plane in pd.planes:
        lines = list(plane.lines)
        planes[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            evs = [(short_name(e.name), float(e.start_ns), float(e.end_ns))
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.setdefault(e.name, []).append(
                            (float(e.start_ns), float(e.end_ns)))
    return {"devices": devices, "spans": spans, "planes": planes}


def short_name(hlo: str) -> str:
    """An operation's name and result shape, without its operands
    ("%fusion.17 = s32[393216]")."""
    return hlo.split("{", 1)[0].split("(", 1)[0].strip()[:120]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: float, hi: float) -> float:
    """Length of the part of disjoint `merged` inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list:
    """The idle (start, end) stretches of [lo, hi] between `merged`."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window_of(ex: dict) -> tuple:
    (lo, hi), = ex["spans"][SPAN_WINDOW]
    return lo, hi


def device_busy(ex: dict) -> list:
    """Per chip, the union of its operations' intervals."""
    return [union((s, e) for _, s, e in evs)
            for evs in ex["devices"].values()]


def busy_s(ex: dict) -> float:
    """Seconds in the window in which an operation ran, averaged over the
    chips used."""
    lo, hi = window_of(ex)
    per = [overlap(m, lo, hi) for m in device_busy(ex)]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_s(ex: dict) -> float:
    lo, hi = window_of(ex)
    return (hi - lo) / 1e9


def idle_share(ex: dict) -> float | None:
    w = window_s(ex)
    if w <= 0 or not ex["devices"]:
        return None
    return 1.0 - busy_s(ex) / w


def call_host_ms(ex: dict) -> list:
    """Per backend call inside the window: its wall time minus the time a
    device operation ran inside it, in ms."""
    lo, hi = window_of(ex)
    busy = device_busy(ex)
    out = []
    for s, e in ex["spans"].get(SPAN_CALL, []):
        if s < lo or s > hi:
            continue
        dev = sum(overlap(m, s, e) for m in busy) / max(len(busy), 1)
        out.append((e - s - dev) / 1e6)
    return out


def top_ops(ex: dict, n: int = 10) -> list:
    """[name, seconds] of the device operations that took most time in the
    window, summed over chips (averaged per chip)."""
    lo, hi = window_of(ex)
    tot: dict = {}
    for evs in ex["devices"].values():
        for name, s, e in evs:
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    k = max(len(ex["devices"]), 1)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, d / k / 1e9] for name, d in rows]


def idle_gaps(ex: dict, n: int = 10) -> list:
    """The device's idle time split by what the host was doing: totals
    inside and outside a backend call, then the longest single gaps, each
    [label, seconds]."""
    lo, hi = window_of(ex)
    calls = union(ex["spans"].get(SPAN_CALL, []))
    merged = union(s for m in device_busy(ex) for s in m)
    inside = outside = 0.0
    rows = []
    for s, e in gaps(merged, lo, hi):
        i = overlap(calls, s, e)
        inside += i
        outside += (e - s) - i
        rows.append(["idle in backend call" if i >= (e - s) / 2
                     else "idle outside backend call", (e - s) / 1e9])
    rows.sort(key=lambda r: -r[1])
    return ([["idle in backend call, total", inside / 1e9],
             ["idle outside backend call, total", outside / 1e9]]
            + rows[:max(0, n - 2)])
