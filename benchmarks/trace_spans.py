"""The program's own spans and counters over one window of a benchmark cell,
read from a profiler trace on the chip.

    python -m benchmarks.trace_spans --workload paper45g.rare-bulk \\
        --seed <n> --seconds 30 [--fixture out.json]

Sets the cell up as `bench/run.py` does (`bench.lib.harness.Session`),
turns on the program's collection spans (`repro.obs.trace_gc`), and runs
one closed-loop window under the profiler.  The trace holds the device's
operations, the benchmark's `bench.*` spans and the program's `repro.*`
spans (`repro/obs.py`), all on one clock.  The last line of standard output
is one JSON object:

* `metrics`: front-door queue wait, front-door host time per micro-batch,
  planning and row-building/tensorizing time per backend call, the bucket
  steps' live share of their padded slabs, the share of their banded rows
  run in the kernels' packed layout, the device's idle share under a
  garbage collection, and first runs of a step in the window;
* `phases_ms`: per backend call (median), the time in each shard-side span;
* `idle_by_span`: the device's idle seconds, each put down to the
  innermost program span open at that instant (`repro.gc` on any thread
  first, then the shard threads' spans, then the dispatcher's; `none`
  where no span is open);
* `checks`: the new numbers against the benchmark's own (Little's law for
  the queue; the shard spans against `engine.host_ms`);
* `device_ops`: the device operations that took most of the window, and
  `ops_by_kind` the same time summed by kind (all fusions, each kernel).

`--fixture` also records a two-second window and writes its reduced trace
(for the tests of the functions here).  The functions below work on the
reduced trace alone, so a test can feed them a recorded one.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
import warnings

from bench.lib import trace as btrace
from bench.lib.stats import median

GC = "repro.gc"
FRONT = "repro.front."


def program_spans(path: str) -> list:
    """[name, line, start_ns, end_ns, meta] of every `repro.*` host event
    of the `.xplane.pb` at `path`; `line` names the host thread's line."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for li, ln in enumerate(plane.lines):
                for e in ln.events:
                    if e.name.startswith("repro."):
                        out.append([e.name, f"{plane.name}#{li}",
                                    float(e.start_ns), float(e.end_ns),
                                    dict(e.stats)])
    return out


def _in_window(ex: dict, name: str) -> list:
    lo, hi = btrace.window_of(ex)
    return [s for s in ex["prog"] if s[0] == name and lo <= s[2] <= hi]


def children(ex: dict, parent: str, kids=None) -> list:
    """For each `parent` span that starts in the window: (its duration,
    {child name: summed duration}) in ns, over the spans on its own line
    that lie inside it (of the names in `kids`, or all)."""
    by_line: dict = {}
    for s in ex["prog"]:
        if s[0] != parent and (kids is None or s[0] in kids):
            by_line.setdefault(s[1], []).append(s)
    for spans in by_line.values():
        spans.sort(key=lambda s: s[2])
    out = []
    for _, line, ps, pe, _ in _in_window(ex, parent):
        spans = by_line.get(line, [])
        i = bisect.bisect_left([s[2] for s in spans], ps)
        tot: dict = {}
        for name, _, s, e, _ in spans[i:]:
            if s > pe:
                break
            if e <= pe:
                tot[name] = tot.get(name, 0.0) + (e - s)
        out.append((pe - ps, tot))
    return out


def front_host_ms(ex: dict) -> float | None:
    """Median per micro-batch of its time outside coalescing and the
    backend call: planning, merging and fulfilling on the dispatcher."""
    rows = children(ex, FRONT + "batch",
                    {FRONT + "coalesce", FRONT + "execute"})
    return median([(d - sum(k.values())) / 1e6 for d, k in rows]) \
        if rows else None


def call_phase_ms(ex: dict, names) -> float | None:
    """Median per backend call of the summed time of its `names` spans."""
    rows = children(ex, "repro.engine.search_batch", set(names))
    return median([sum(k.values()) / 1e6 for _, k in rows]) if rows else None


def phases_ms(ex: dict) -> dict:
    """Median per backend call of the time in each shard-side phase (a
    phase nested in another, such as the step inside a first run, is
    counted in both)."""
    rows = children(ex, "repro.engine.search_batch")
    names = sorted({n for _, k in rows for n in k})
    out = {n: median([k.get(n, 0.0) / 1e6 for _, k in rows]) for n in names}
    if rows:
        out["repro.engine.search_batch"] = median([d / 1e6 for d, _ in rows])
    return out


def idle_by_span(ex: dict) -> list:
    """[[label, seconds], ...]: the device's idle time in the window, each
    instant put down to the innermost program span open then.  A
    collection (`repro.gc`, any thread) comes first, then the shard
    threads' spans, then the dispatcher's; `none` where none is open."""
    lo, hi = btrace.window_of(ex)
    busy = btrace.union(s for m in btrace.device_busy(ex) for s in m)
    ev = []
    for i, (name, _, s, e, _) in enumerate(ex["prog"]):
        if e > lo and s < hi:
            ev += [(s, 1, i), (e, -1, i)]
    for s, e in btrace.gaps(busy, lo, hi):
        ev += [(s, 2, -1), (e, -2, -1)]
    ev.sort(key=lambda x: (x[0], x[1]))
    open_: list = [{}, {}, {}]          # gc, shard, dispatcher: i -> start

    def klass(name):
        return 0 if name == GC else 2 if name.startswith(FRONT) else 1

    def label():
        # innermost: the latest start; of spans starting together, the
        # first to end
        for k in open_:
            if k:
                return ex["prog"][max(k, key=lambda i: (
                    k[i], -ex["prog"][i][3]))][0]
        return "none"

    tot: dict = {}
    idle, last = False, lo
    for t, kind, i in ev:
        if idle and t > last:
            lab = label()
            tot[lab] = tot.get(lab, 0.0) + (t - last)
        last = max(last, t)
        if kind == 2:
            idle = True
        elif kind == -2:
            idle = False
        elif kind == 1:
            open_[klass(ex["prog"][i][0])][i] = ex["prog"][i][2]
        else:
            open_[klass(ex["prog"][i][0])].pop(i, None)
    return sorted(([k, v / 1e9] for k, v in tot.items()),
                  key=lambda r: -r[1])


def counter_metrics(c0: dict, c1: dict) -> dict:
    """The counter metrics over the window from its edge snapshots."""
    def d(k):
        return c1[k] - c0[k]
    out = {}
    if d("dequeued") > 0:
        out["front.queue_wait_ms"] = 1e3 * d("queue_wait_s") / d("dequeued")
    if d("slab_elems") > 0:
        out["step.live_share"] = 100.0 * d("live_elems") / d("slab_elems")
    if "banded_rows" in c1 and d("banded_rows") > 0:
        out["step.packed_share"] = 100.0 * d("packed_rows") / d("banded_rows")
    out["jit.first_runs"] = d("first_runs")
    return out


def ops_by_kind(ex: dict) -> list:
    """[kind, seconds] of the window's device operations summed by kind:
    the instruction name without `%`, its `.N` and its shape (`fusion`,
    `sort`, a Pallas kernel's name such as `intersect_packed`)."""
    tot: dict = {}
    for name, sec in btrace.top_ops(ex, n=1 << 30):
        kind = re.match(r"%?([A-Za-z_-]+)", name)
        k = kind.group(1) if kind else name
        tot[k] = tot.get(k, 0.0) + sec
    return sorted(([k, v] for k, v in tot.items()), key=lambda r: -r[1])


def trace_metrics(ex: dict) -> dict:
    """The trace metrics of the window (missing where nothing was read)."""
    idle = idle_by_span(ex)
    total = sum(v for _, v in idle)
    out = {
        "front.host_ms": front_host_ms(ex),
        "engine.plan_ms": call_phase_ms(ex, ["repro.engine.plan"]),
        "engine.tensorize_ms": call_phase_ms(
            ex, ["repro.batch.rows", "repro.batch.tensorize",
                 "repro.batch.transfer"]),
        "device.idle_gc_share": (100.0 * dict(idle).get(GC, 0.0) / total
                                 if total > 0 else None),
    }
    return {k: v for k, v in out.items() if v is not None}


def checks(ex: dict, metrics: dict, in_flight: int,
           throughput: float) -> dict:
    """The new numbers against the benchmark's: (queue wait + median
    micro-batch) over in_flight / throughput (Little's law), and the
    shard-side span time outside `repro.batch.fetch` per call over
    `engine.host_ms` (both medians)."""
    out = {}
    batch = [d / 1e6 for d, _ in children(ex, FRONT + "batch", set())]
    if batch and throughput > 0 and "front.queue_wait_ms" in metrics:
        out["little_ratio"] = ((metrics["front.queue_wait_ms"]
                                + median(batch))
                               / (1e3 * in_flight / throughput))
        out["batch_ms"] = median(batch)
    calls = children(ex, "repro.engine.search_batch", {"repro.batch.fetch"})
    host = btrace.call_host_ms(ex)
    if calls and host:
        out["outside_fetch_ms"] = median(
            [(d - k.get("repro.batch.fetch", 0.0)) / 1e6 for d, k in calls])
        out["engine_host_ms"] = median(host)
        out["outside_fetch_over_host"] = (out["outside_fetch_ms"]
                                          / out["engine_host_ms"])
    idle = dict(idle_by_span(ex))
    total = sum(idle.values())
    if total > 0:
        out["idle_none_share"] = idle.get("none", 0.0) / total
    return out


def spans_per_call(ex: dict) -> float | None:
    """Shard-side spans that start in the window, per backend call."""
    lo, hi = btrace.window_of(ex)
    calls = _in_window(ex, "repro.engine.search_batch")
    n = sum(1 for s in ex["prog"] if lo <= s[2] <= hi
            and not s[0].startswith(FRONT) and s[0] != GC)
    return n / len(calls) if calls else None


# -- the run on the chip --------------------------------------------------


def _counters(sess, gc_stats) -> dict:
    st = sess.front.stats
    c = dict(sess.backend.inner.engine.batch_executor.slab_stats)
    c.update(dequeued=st.dequeued, queue_wait_s=st.queue_wait_s,
             submitted=st.submitted, batches=st.batches,
             gc_collections=gc_stats.collections, gc_pause_s=gc_stats.pause_s)
    return c


def _window(sess, seconds: float, tdir: str, gc_stats) -> dict:
    """One traced closed-loop window: the reduced trace, its counters at
    the window's edges and the window's EXACT answers per second."""
    import shutil

    from bench.lib import harness
    from repro.core.api import STATUS_SERVED_EXACT
    jax = sess.jax
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    sess.backend.calls.clear()
    c0 = _counters(sess, gc_stats)
    sent, w0, w1 = harness.run_closed(
        sess.front, sess.reqs, sess.order, sess.mix["arrivals"]["in_flight"],
        seconds, jax.profiler.TraceAnnotation)
    c1 = _counters(sess, gc_stats)
    jax.profiler.stop_trace()
    path = btrace.find_xplane(tdir)
    ex = btrace.extract(path)
    ex["prog"] = program_spans(path)
    shutil.rmtree(tdir, ignore_errors=True)
    exact = {id(r.request) for r in sent if r.resp is not None
             and r.resp.status == STATUS_SERVED_EXACT}
    ex.pop("planes", None)
    ex.update(c0=c0, c1=c1, seconds=float(seconds),
              in_flight=sess.mix["arrivals"]["in_flight"],
              throughput_qps=harness.exact_work(sess.backend.calls, exact,
                                                w0, w1) / seconds)
    return ex


def report(ex: dict) -> dict:
    m = counter_metrics(ex["c0"], ex["c1"])
    m.update(trace_metrics(ex))
    return {"metrics": m, "phases_ms": phases_ms(ex),
            "idle_by_span": idle_by_span(ex),
            "checks": checks(ex, m, ex["in_flight"], ex["throughput_qps"]),
            "spans_per_call": spans_per_call(ex),
            "throughput_qps": ex["throughput_qps"],
            "idle_share": btrace.idle_share(ex),
            "device_ops": btrace.top_ops(ex),
            "ops_by_kind": ops_by_kind(ex)[:12],
            "gc": {"collections": ex["c1"]["gc_collections"]
                   - ex["c0"]["gc_collections"],
                   "pause_s": ex["c1"]["gc_pause_s"]
                   - ex["c0"]["gc_pause_s"]}}


def _compact(ex: dict) -> dict:
    """The reduced trace with whole-ns times, for a fixture."""
    return dict(
        ex, devices={k: [[n, int(s), int(e)] for n, s, e in v]
                     for k, v in ex["devices"].items()},
        spans={k: [[int(s), int(e)] for s, e in v]
               for k, v in ex["spans"].items()},
        prog=[[n, ln, int(s), int(e), m] for n, ln, s, e, m in ex["prog"]])


def main() -> None:
    import argparse
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args()
    from bench.lib import harness, spec
    from repro import obs
    bench = spec.benchmark(root)
    cell = spec.cell(bench, args.workload)
    gc_stats = obs.trace_gc()
    sess = harness.Session(spec.config(cell["config"]),
                           spec.mix(cell["traffic"]), args.seed,
                           cell["chips"])
    tdir = str(root / "bench" / ".trace" / "spans")
    ex = _window(sess, args.seconds, tdir, gc_stats)
    out = report(ex)
    if args.fixture:
        short = _window(sess, 2.0, tdir, gc_stats)
        short["kind"] = sess.device["kind"]
        with open(args.fixture, "w") as f:
            json.dump(_compact(short), f, separators=(",", ":"))
    sess.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
