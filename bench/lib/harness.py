"""One run of one cell: set-up, the measured window, the metrics and the
check against the plain reference.

The system under test is the program's served path: `serve.FrontDoor`
over one `serve.ShardBackend` (the platform's kernels: compiled Pallas on a
TPU), fed by the benchmark's own corpus, built with the program's
`build_all`.  The benchmark wraps the backend in `TimedBackend`, passed in
through the front door's public `backends=` argument, to time each backend
call and mark it in the profiler's trace.

Set-up (`setup_s`, from process start to the window's start): the
program's import, the data draw, the index (loaded from the checkout's
cache, `bench/lib/index_cache.py`, or built and saved there), the arena's
transfer to the device, and the warm-up (`warm_batches`, `precompile`,
`warm`), which loads or compiles, and runs once, every program the window
can run.

The window is a closed loop: `in_flight` clients, each sends its next
request when its last one is answered.  Afterwards every answer of the
window is compared with the plain reference's answer to its query.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time

import numpy as np

from bench.lib import check, corpus as bcorpus, index_cache, traffic
from bench.lib.reference import Reference


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class TimedBackend:
    """The front door's backend, timed: each call's host-clock start and
    end, the requests it carried, and a `bench.backend_call` span in the
    profiler's trace."""

    def __init__(self, inner, annotate):
        self.inner = inner
        self.annotate = annotate
        self.calls: list = []          # (start, end, [id(request)])
        self.fault = None              # tests only: alters what comes back

    def __call__(self, requests):
        t0 = time.monotonic()
        with self.annotate("bench.backend_call"):
            out = self.inner(requests)
        t1 = time.monotonic()
        self.calls.append((t0, t1, [id(r) for r in requests]))
        if self.fault is not None:
            out = self.fault(requests, out)
        return out


@dataclasses.dataclass
class Sent:
    """One request of the window."""
    pool_i: int
    sent: float
    request: object
    done: float | None = None
    resp: object = None


class GcPauses:
    """The interpreter's garbage-collection pauses while `armed`."""

    def __init__(self):
        self.armed = False
        self.pauses: list = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self.armed:
            self.pauses.append((info["generation"],
                                time.monotonic() - self._t))


class CompileCounter:
    """Backend compiles (jax.monitoring): all of them, and those while
    `armed` (the window)."""

    def __init__(self):
        self.armed = False
        self.total = 0
        self.compiles = 0

    def install(self, jax):
        def on_duration(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.total += 1
                self.compiles += self.armed
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def to_request(SearchRequest, q: traffic.Query):
    return SearchRequest(q.words, mode=q.mode)


def build_world(cfg: dict, *, cache: bool = True):
    """The deployment's data, drawn from the configuration's `data_seed`,
    and the program's index over it (from the checkout's index cache where
    `cache`).  The program's analyzer is drawn by the program from the same
    settings and must equal the benchmark's copy."""
    t0 = time.monotonic()
    seed = cfg["data_seed"]
    lex = bcorpus.lexicon_from(cfg, seed)
    forms = bcorpus.draw_forms(lex)
    corp = bcorpus.corpus_from(cfg, lex, forms, seed)
    log(f"set-up: data draw {time.monotonic() - t0:.3f} s ({corp.n_docs} "
        f"docs, {corp.n_tokens} tokens)")

    def build():
        from repro.core import (IndexParams, LexiconConfig, build_all,
                                make_lexicon_and_analyzer)
        from repro.core.corpus import Corpus
        t1 = time.monotonic()
        plex, pana = make_lexicon_and_analyzer(LexiconConfig(
            n_surface=lex.n_surface, n_base=lex.n_base, n_stop=lex.n_stop,
            n_frequent=lex.n_frequent, multi_form_frac=lex.multi_form_frac,
            zipf_s=lex.zipf_s, seed=lex.seed))
        index = build_all(Corpus(doc_offsets=corp.doc_offsets,
                                 tokens=corp.tokens), plex, pana,
                          IndexParams(**cfg["index"]))
        log(f"set-up: index build {time.monotonic() - t1:.3f} s")
        return index

    index = (index_cache.load_or_build(cfg, build, log)[0] if cache
             else build())
    if not (np.array_equal(index.analyzer.primary, forms.primary)
            and np.array_equal(index.analyzer.secondary, forms.secondary)):
        raise RuntimeError("the program's analyzer differs from the "
                           "benchmark's draw of it")
    return lex, forms, corp, index


def _pow2_sizes(max_batch: int) -> list:
    sizes = [1]
    while sizes[-1] < max_batch:
        sizes.append(min(2 * sizes[-1], max_batch))
    return sizes


def warm_batches(engine, reqs: list, max_batch: int) -> tuple:
    """(batches, steps): the batches of pool indices the warm-up runs, and
    their bucket steps lowered (`BatchExecutor.lower_steps`).

    A bucket step's shape is its class (static arguments and every table
    dimension but the first) and its row count T: a query's rows of a
    class, r, padded to a power of two of at least 4.  A batch of n copies
    of one query has n * r rows in each of its classes, so copies at n = 1,
    2, 4, .. step T through every power of two from the query's own T up.
    One such ladder, to n = max_batch, for each (class, T) that a query of
    the pool shows alone, covers the T of every micro-batch of up to
    max_batch pool queries, from the least single T of a class to
    max_batch times its largest r.  As T hides r (r > T / 4 only), the
    ladder of each class's largest T goes on to n = 4 * max_batch."""
    be = engine.batch_executor
    plans = [engine.plan_request(r) for r in reqs]

    def lower(ix):
        return be.lower_steps([plans[i] for i in ix], [reqs[i] for i in ix])

    steps, first, top = {}, {}, {}
    for i in range(len(reqs)):
        for key, low in lower([i]).items():
            steps.setdefault(key, low)
            static, shapes = key
            cls = (static, tuple((name, shape[1:]) for name, shape in shapes))
            t = shapes[0][1][0]
            first.setdefault((cls, t), i)
            top[cls] = max(top.get(cls, 0), t)
    reach = {i: max_batch for i in first.values()}
    for cls, t in top.items():
        reach[first[(cls, t)]] = 4 * max_batch
    batches = [[i] * n for i in sorted(reach) for n in _pow2_sizes(reach[i])]
    for ix in batches:
        if len(ix) > 1:
            for key, low in lower(ix).items():
                steps.setdefault(key, low)
    return batches, steps


def precompile(steps: dict) -> None:
    """Compile the lowered steps concurrently.  The chip's compiler runs
    outside the GIL, so a cold start compiles a CPU's worth of programs at
    a time; a warm one loads them from JAX's cache."""
    import os
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        list(pool.map(lambda low: low.compile(), steps.values()))


def warm(FrontDoor, FrontDoorConfig, index, backend, reqs: list,
         batches: list, max_batch: int, max_queue: int) -> None:
    """Run each warm-up batch through the backend, so that every program
    the window can call has run once in this process, then the pool
    through a front door in micro-batches of `max_batch`."""
    for ix in batches:
        backend([reqs[i] for i in ix])
    front = FrontDoor(index, backends=[backend], cfg=FrontDoorConfig(
        max_batch=max_batch, batch_window_ms=250.0,
        default_deadline_ms=3.6e6, cache_capacity=0, shard_timeout_s=3600.0,
        max_queue=max(max_queue, len(reqs))))
    try:
        for lo in range(0, len(reqs), max_batch):
            front.search_batch(reqs[lo:lo + max_batch])
    finally:
        front.close()


def _waiter(ticket, rec: Sent, timeout: float):
    try:
        rec.resp = ticket.result(timeout)
        rec.done = time.monotonic()
    except TimeoutError:
        pass


def run_closed(front, reqs: list, order, in_flight: int, seconds: float,
               annotate) -> tuple:
    """Closed loop: `in_flight` clients, each sends its next request (the
    next entry of `order`) when its last one is answered, until the window
    closes; the answers to requests in flight then are still awaited.
    Returns (sent, t0, t1)."""
    lock = threading.Lock()
    nxt = [0]
    sent: list = []
    t0 = time.monotonic() + 0.05
    t1 = t0 + seconds

    def client():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            k = int(order[i % len(order)])
            now = time.monotonic()
            if now >= t1:
                return
            if now < t0:
                time.sleep(t0 - now)
            rec = Sent(pool_i=k, sent=time.monotonic(),
                       request=dataclasses.replace(reqs[k]))
            ticket = front.submit(rec.request)
            _waiter(ticket, rec, 120.0)
            with lock:
                sent.append(rec)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(in_flight)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    with annotate("bench.window"):
        time.sleep(max(0.0, t1 - time.monotonic()))
    for th in threads:
        th.join(timeout=180.0)
    return sent, t0, t1


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def _front_stats(front) -> dict:
    st = front.stats
    return {k: getattr(st, k) for k in (
        "submitted", "served_exact", "served_degraded", "shed",
        "cache_hits", "flex_routed", "batches")}


def exact_work(calls: list, exact: set, w0: float, w1: float) -> float:
    """Requests answered EXACT in [w0, w1]: each backend call's EXACT
    requests (`exact`: their ids), counted by the share of the call's time
    that lies inside the window, so that a micro-batch straddling an edge
    counts for the part of its work done inside."""
    work = 0.0
    for s, e, ids in calls:
        inside = min(e, w1) - max(s, w0)
        if inside > 0:
            work += sum(i in exact for i in ids) * inside / (e - s)
    return work


def _bytes_per_posting(engine) -> float:
    """Packed device bytes per posting of the served arena."""
    dev = engine.batch_executor.dev
    return float(dev.device_nbytes()) / float(dev.arena_real_np.sum())


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_start: float, *,
             require_tpu: bool = True, fault=None, trace_dir=None,
             peaks: dict | None = None, cache_index: bool = True):
    """One run; returns (result, ctx).  `fault` (tests only) alters what
    the backend returns, beneath the front door."""
    sess = Session(cfg, mix, seed, cell["chips"], require_tpu=require_tpu,
                   peaks=peaks, cache_index=cache_index)
    sess.backend.fault = fault
    win = sess.window(seconds, trace=trace, trace_dir=trace_dir)
    ctx = sess.context(win, t_start)
    sess.close()

    from bench.lib import spec as bspec
    metrics = {}
    for m in bspec.metrics_for(spec, cell["name"], trace):
        v = bspec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    compared = sess.check(win)
    result = {"correct": check.is_correct(compared),
              "attempted": len(win.sent),
              "failed": len(win.sent) - win.n_exact, "metrics": metrics,
              "device": dict(sess.device, memory_peak_bytes=win.peak_bytes)}
    if trace:
        from bench.lib import trace as btrace
        result["device"]["busy_s"] = btrace.busy_s(win.trace)
        result["device"]["window_s"] = btrace.window_s(win.trace)
        result["breakdown"] = {"device_ops": btrace.top_ops(win.trace),
                               "idle_gaps": btrace.idle_gaps(win.trace)}
    result["compared"] = compared
    return result, ctx


class Session:
    """Set-up, the measured window, then the check against the
    reference."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, *,
                 require_tpu: bool = True, peaks: dict | None = None,
                 cache_index: bool = True):
        import jax
        devs = jax.devices()
        platform, kind = devs[0].platform, devs[0].device_kind
        if require_tpu and platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform {platform!r})")
        if len(devs) < chips:
            raise NoChip(f"{chips} chips needed, JAX finds {len(devs)}")
        if mix["arrivals"]["loop"] != "closed":
            raise ValueError("the harness drives closed loops only")
        if peaks is None:
            from bench.lib import spec as bspec
            peaks = bspec.peaks(kind)
        if require_tpu:
            from repro.launch.compile_cache import enable_compile_cache
            log(f"compile cache: {enable_compile_cache()}")
        log(f"devices: {len(devs)} x {platform}/{kind}")
        from repro.core import SearchRequest
        from repro.serve import FrontDoor, FrontDoorConfig, ShardBackend
        self.jax, self.dev0 = jax, devs[0]
        self.device = {"platform": platform, "kind": kind,
                       "count": len(devs)}
        self.cfg, self.mix, self.seed, self.peaks = cfg, mix, seed, peaks
        self.counter = CompileCounter()
        self.counter.install(jax)
        self.gc = GcPauses()
        gc.callbacks.append(self.gc)
        self.lex, self.forms, self.corp, self.index = build_world(
            cfg, cache=cache_index)
        # the pool, like the data, belongs to the deployment: every seed
        # sends the same queries, in its own order
        self.pool = traffic.make_pool(mix, self.corp, self.lex, self.forms,
                                      cfg["data_seed"])
        self.reqs = [to_request(SearchRequest, q) for q in self.pool]
        self.backend = TimedBackend(ShardBackend(self.index),
                                    jax.profiler.TraceAnnotation)
        engine = self.backend.inner.engine
        fcfg = mix["front"]
        t0 = time.monotonic()
        jax.block_until_ready(engine.batch_executor.dev.device_arena)
        log(f"set-up: device_put of the arena {time.monotonic() - t0:.3f} s")
        t0 = time.monotonic()
        batches, steps = warm_batches(engine, self.reqs, fcfg["max_batch"])
        log(f"set-up: lowering {time.monotonic() - t0:.3f} s ({len(steps)} "
            f"bucket steps; {len(batches)} warm-up batches, "
            f"{sum(map(len, batches))} requests)")
        t0 = time.monotonic()
        precompile(steps)
        log(f"set-up: precompile {time.monotonic() - t0:.3f} s "
            f"({self.counter.total} backend compiles)")
        t0 = time.monotonic()
        warm(FrontDoor, FrontDoorConfig, self.index, self.backend, self.reqs,
             batches, fcfg["max_batch"], fcfg.get("max_queue", 512))
        log(f"set-up: warm pass {time.monotonic() - t0:.3f} s "
            f"({self.counter.total} backend compiles in all)")
        stats = self.dev0.memory_stats() or {}
        log(f"set-up: bytes_in_use "
            f"{stats.get('bytes_in_use', 'not reported')}")
        self.bytes_per_posting = _bytes_per_posting(engine)
        self.front = FrontDoor(self.index, backends=[self.backend],
                               cfg=FrontDoorConfig(cache_capacity=0, **fcfg))
        self.order = traffic.pool_order(len(self.reqs), 1 << 20, seed)

    def window(self, seconds: float, *, trace: bool = False, trace_dir=None):
        """The measured window; returns its record."""
        import shutil
        import types
        from repro.core.api import STATUS_SERVED_EXACT
        jax, front = self.jax, self.front
        if trace:
            tdir = str(trace_dir)
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        self.backend.calls.clear()
        s0 = _front_stats(front)
        self.counter.compiles = 0
        self.counter.armed = True
        self.gc.pauses.clear()
        self.gc.armed = True
        sent, w0, w1 = run_closed(front, self.reqs, self.order,
                                  self.mix["arrivals"]["in_flight"], seconds,
                                  jax.profiler.TraceAnnotation)
        self.counter.armed = False
        self.gc.armed = False
        ex = None
        if trace:
            jax.profiler.stop_trace()
        s1 = _front_stats(front)
        peak = (self.dev0.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"window: {len(sent)} requests in {seconds} s; front stats "
            f"{ {k: s1[k] - s0[k] for k in s1} }; "
            f"{self.counter.compiles} backend compiles")
        calls = [c for c in self.backend.calls if w0 <= c[0] <= w1]
        longest = sorted(calls, key=lambda c: c[0] - c[1])[:3]
        log("window: longest backend calls " + ", ".join(
            f"{1e3 * (e - s):.1f} ms ({len(ids)} req)"
            for s, e, ids in longest))
        gcs = self.gc.pauses
        if gcs:
            log(f"window: {len(gcs)} gc pauses, {1e3 * sum(d for _, d in gcs):.1f}"
                f" ms in all, longest {1e3 * max(d for _, d in gcs):.1f} ms "
                f"(generation {max(gcs, key=lambda g: g[1])[0]})")
        if trace:
            from bench.lib import trace as btrace
            ex = btrace.extract(btrace.find_xplane(tdir))
            shutil.rmtree(tdir, ignore_errors=True)
            log(f"trace planes: {ex['planes']}")
        exact = {id(r.request) for r in sent if r.resp is not None
                 and r.resp.status == STATUS_SERVED_EXACT}
        return types.SimpleNamespace(
            sent=sent, w0=w0, w1=w1, s0=s0, s1=s1,
            compiles=self.counter.compiles, trace=ex, peak_bytes=peak,
            seconds=float(seconds), calls=calls, n_exact=len(exact),
            exact_in_window=exact_work(self.backend.calls, exact, w0, w1))

    def context(self, win, t_start: float):
        """What the metric readers read (bench/metrics/*.py)."""
        import types
        return types.SimpleNamespace(
            setup_s=win.w0 - t_start, seconds=win.seconds, sent=win.sent,
            calls=win.calls, exact_in_window=win.exact_in_window,
            postings_read=sum(int(r.resp.postings_read) for r in win.sent
                              if r.resp is not None and r.done is not None
                              and r.done <= win.w1),
            bytes_per_posting=self.bytes_per_posting, stats0=win.s0,
            stats1=win.s1, compiles=win.compiles, trace=win.trace,
            peaks=self.peaks, w0=win.w0, w1=win.w1)

    def close(self):
        """Free the program's state, so that the reference runs after it."""
        gc.callbacks.remove(self.gc)
        self.front.close()
        self.front = self.backend = self.index = None
        gc.collect()

    def check(self, win) -> dict:
        """Every answer of the window against the plain reference's answer
        to its query (computed once per pool entry)."""
        from repro.core.api import STATUS_SHED
        t0 = time.monotonic()
        ref = Reference(self.corp, self.lex, self.forms, self.cfg["index"])
        truth: dict = {}
        wrong = checked = missing = 0
        for r in win.sent:
            if r.resp is None:
                missing += 1
                continue
            if r.resp.status == STATUS_SHED:
                continue
            if r.pool_i not in truth:
                truth[r.pool_i] = ref.answer(self.pool[r.pool_i])
            why = check.compare(check.served_fields(r.resp),
                                truth[r.pool_i])
            checked += 1
            if why is not None:
                wrong += 1
                if wrong <= 10:
                    log(f"wrong answer: {self.pool[r.pool_i]}: {why}")
        log(f"reference check: {checked} answers of {len(truth)} queries in "
            f"{time.monotonic() - t0:.3f} s")
        return check.judge(wrong, missing, checked)
