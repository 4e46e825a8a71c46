"""The reading of the program's spans and counters from a profiler trace
(`benchmarks/trace_spans.py`): on a hand-made trace whose answers are
known, and on a short trace recorded on a TPU v5e
(`fixtures/trace_spans_v5e.json`: a two-second window of
`paper45g.rare-bulk`, as `trace_spans` reduces it)."""
import json
from pathlib import Path

import pytest

from bench.lib import trace as btrace
from benchmarks import trace_spans as ts

FIXTURE = Path(__file__).parent / "fixtures" / "trace_spans_v5e.json"
A, B, C = "/host:CPU#0", "/host:CPU#1", "/host:CPU#2"


def _hand_made():
    """Window [0, 100]; the device busy in [10, 20] and [50, 60]; the
    dispatcher (A) in one micro-batch, a backend call on a shard thread
    (B), and a collection on a third thread (C)."""
    prog = [["repro.front.batch", A, 0, 100, {}],
            ["repro.front.coalesce", A, 0, 4, {}],
            ["repro.front.execute", A, 5, 95, {}],
            ["repro.engine.search_batch", B, 6, 94, {}],
            ["repro.engine.plan", B, 6, 9, {}],
            ["repro.batch.rows", B, 9, 10, {}],
            ["repro.batch.fetch", B, 10, 30, {}],
            ["repro.batch.tensorize", B, 31, 33, {}],
            ["repro.gc", C, 40, 45, {"generation": 0}]]
    return {"devices": {"/device:TPU:0": [["op", 10, 20], ["op", 50, 60]]},
            "spans": {btrace.SPAN_WINDOW: [[0, 100]],
                      btrace.SPAN_CALL: [[6, 94]]},
            "prog": prog}


def test_idle_by_span_by_hand():
    got = dict(ts.idle_by_span(_hand_made()))
    # idle: [0, 10], [20, 50], [60, 100] (ns); the innermost open span
    # wins, a collection first, then the shard thread, then the dispatcher
    want = {"repro.front.coalesce": 4, "repro.front.batch": 1 + 5,
            "repro.front.execute": 1 + 1, "repro.engine.plan": 3,
            "repro.batch.rows": 1, "repro.batch.fetch": 10,
            "repro.engine.search_batch": 1 + 7 + 5 + 34,
            "repro.batch.tensorize": 2, "repro.gc": 5}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(80 / 1e9)


def test_children_by_hand():
    ex = _hand_made()
    assert ts.front_host_ms(ex) == pytest.approx((100 - 4 - 90) / 1e6)
    assert ts.call_phase_ms(ex, ["repro.engine.plan"]) == \
        pytest.approx(3 / 1e6)
    assert ts.call_phase_ms(ex, ["repro.batch.rows", "repro.batch.tensorize",
                                 "repro.batch.transfer"]) == \
        pytest.approx(3 / 1e6)
    # the collection ran on another thread: not a child of the call
    assert "repro.gc" not in ts.phases_ms(ex)
    assert ts.spans_per_call(ex) == 5


def test_counter_metrics():
    c0 = dict(dequeued=10, queue_wait_s=1.0, slab_elems=1000,
              live_elems=100, first_runs=3)
    c1 = dict(dequeued=74, queue_wait_s=57.0, slab_elems=3000,
              live_elems=150, first_runs=3)
    assert ts.counter_metrics(c0, c1) == pytest.approx(
        {"front.queue_wait_ms": 875.0, "step.live_share": 2.5,
         "jit.first_runs": 0})


def test_counter_metrics_packed_share():
    """`step.packed_share` where the counters have banded rows; absent
    where the program lacks the counter."""
    c0 = dict(dequeued=0, queue_wait_s=0.0, slab_elems=10, live_elems=1,
              first_runs=0, banded_rows=100, packed_rows=40)
    c1 = dict(c0, slab_elems=20, live_elems=2, banded_rows=300,
              packed_rows=220)
    assert ts.counter_metrics(c0, c1)["step.packed_share"] == \
        pytest.approx(90.0)
    old = {k: v for k, v in c0.items() if "banded" not in k}
    assert "step.packed_share" not in ts.counter_metrics(old, old)


@pytest.fixture(scope="module")
def rec():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_on_the_chip(rec):
    assert rec["kind"] == "TPU v5 lite"
    names = {s[0] for s in rec["prog"]}
    assert {"repro.front.batch", "repro.engine.search_batch",
            "repro.batch.step", "repro.batch.fetch"} <= names


def test_idle_by_span_adds_up(rec):
    rows = ts.idle_by_span(rec)
    idle = sum(v for _, v in rows)
    assert idle == pytest.approx(btrace.window_s(rec) - btrace.busy_s(rec),
                                 rel=1e-9)
    assert dict(rows).get("none", 0.0) <= 0.1 * idle


def test_ops_by_kind_adds_up(rec):
    kinds = dict(ts.ops_by_kind(rec))
    total = sum(sec for _, sec in btrace.top_ops(rec, n=1 << 30))
    assert sum(kinds.values()) == pytest.approx(total, rel=1e-9)
    assert kinds["fusion"] > 0
    assert not any("." in k or "%" in k for k in kinds)


def test_recorded_metrics(rec):
    m = ts.counter_metrics(rec["c0"], rec["c1"])
    m.update(ts.trace_metrics(rec))
    for name in ("front.queue_wait_ms", "front.host_ms", "engine.plan_ms",
                 "engine.tensorize_ms", "step.live_share",
                 "device.idle_gc_share", "jit.first_runs"):
        assert name in m, name
    assert 0.0 < m["step.live_share"] < 100.0
    assert 0.0 <= m["device.idle_gc_share"] < 100.0
    # the shard-side spans hold the call's host time
    calls = ts.children(rec, "repro.engine.search_batch")
    assert calls and all(sum(k.values()) <= 2 * d for d, k in calls)
