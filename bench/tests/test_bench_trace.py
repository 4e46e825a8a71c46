"""The reduction from a profiler trace to device metrics, on a short trace
recorded on a TPU v5e (`fixtures/trace_v5e.json`: the device operations
and the benchmark's own spans of a one-second window, as
`bench.lib.trace.extract` keeps them)."""
import json
import types
from pathlib import Path

import pytest

from bench.lib import spec, trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e.json"


@pytest.fixture(scope="module")
def rec():
    with open(FIXTURE) as f:
        return json.load(f)


def _sweep_busy(intervals, lo, hi):
    """Covered length of [lo, hi] by a sweep over sorted endpoints: the
    same quantity as trace.union + overlap, computed another way."""
    ev = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            ev += [(s, 1), (e, -1)]
    ev.sort()
    depth, last, total = 0, None, 0.0
    for t, d in ev:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_on_the_chip(rec):
    assert rec["kind"] == "TPU v5 lite"
    assert rec["devices"] and rec["spans"][trace.SPAN_CALL]


def test_busy_and_idle_share(rec):
    lo, hi = trace.window_of(rec)
    (evs,) = rec["devices"].values()
    want = _sweep_busy([(s, e) for _, s, e in evs], lo, hi) / 1e9
    assert trace.busy_s(rec) == pytest.approx(want, rel=1e-12)
    share = trace.idle_share(rec)
    assert 0.0 < share < 1.0
    assert share == pytest.approx(1.0 - want / trace.window_s(rec))


def test_idle_gaps_add_up(rec):
    gaps = trace.idle_gaps(rec)
    idle = gaps[0][1] + gaps[1][1]
    assert idle == pytest.approx(trace.window_s(rec) - trace.busy_s(rec),
                                 rel=1e-9)
    assert len(gaps) <= 10


def test_host_time_per_call(rec):
    ms = trace.call_host_ms(rec)
    assert ms and all(m >= 0 for m in ms)
    walls = [(e - s) / 1e6 for s, e in rec["spans"][trace.SPAN_CALL]]
    assert max(ms) <= max(walls)


def test_hbm_share(rec):
    peaks = spec.peaks(rec["kind"])
    ctx = types.SimpleNamespace(trace=rec, postings_read=rec["postings_read"],
                                bytes_per_posting=rec["bytes_per_posting"],
                                peaks=peaks)
    got = spec.reader("step.hbm_share.bulk")(ctx)
    want = (100.0 * rec["postings_read"] * rec["bytes_per_posting"]
            / peaks["hbm_bytes_per_s"] / trace.busy_s(rec))
    assert got == pytest.approx(want)
    assert 0.0 < got <= 100.0
    ctx.trace = None
    assert spec.reader("step.hbm_share.bulk")(ctx) is None
