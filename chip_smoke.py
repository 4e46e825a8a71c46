#!/usr/bin/env python3
"""Chip smoke: the served search path, end to end, on a TPU.

    python chip_smoke.py                    # one chip: front door + serve tier
    python chip_smoke.py --four-chips       # SearchServe over a 4-chip mesh
    python chip_smoke.py --cpu-rehearsal    # the same phases, tiny, on the CPU

One process drives everything.  The default run builds the benchmark's
canonical world (`benchmarks.common.bench_world`: 1200 docs of ~800 tokens
at a 0.4 stop share, from --seed), serves phrase, near, ranked near and
K-word requests through `serve.front.FrontDoor` with its default backend
(the compiled Pallas kernels on a TPU), and requires that:

  * every response is SERVED_EXACT and bit-identical (postings accounting
    and float32 scores included) to `AdditionalIndexEngine(index,
    batch_impl="ref").search_batch` in the same process;
  * the single-device `SearchServe` tier returns the same bits;
  * at least 64 responses agree with the brute-force oracles;
  * one served bucket step of each kind (phrase, ranked, kword) compiles to
    a program that holds `tpu_custom_call`, i.e. the Pallas kernels.

`--four-chips` runs only `SearchServe` doc-partitioned over
`make_host_mesh(data=4)`, compared bit for bit with the one-chip engine,
and prints each device's bytes in use.

The last line of standard output is one JSON object; it reads
`"ok": true` only when every check passed.  Without a TPU (and without
--cpu-rehearsal) the script exits non-zero and prints no result.  Timings
printed on earlier lines are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
BATCH = 64                 # requests per front-door micro-batch / serve call


def _log(msg: str) -> None:
    print(msg, flush=True)


def _die(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, lexicon and query streams")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip doc-partitioned SearchServe "
                         "check")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same phases at a tiny size on the CPU, "
                         "Pallas in interpret mode (never a chip pass)")
    return ap.parse_args()


class Checks:
    """Counts failed checks; every failure is printed as it happens."""

    def __init__(self):
        self.failed = 0

    def __call__(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed += 1
            _log(f"  CHECK FAILED: {what}")
        return ok


def same_response(r1, r2) -> bool:
    """Bit-identity of two SearchResponses: anchors, postings accounting,
    fallback flags and (ranked) float32 scores."""
    import numpy as np
    same = (np.array_equal(r1.doc, r2.doc) and np.array_equal(r1.pos, r2.pos)
            and r1.postings_read == r2.postings_read
            and r1.used_fallback == r2.used_fallback
            and r1.doc_only == r2.doc_only
            and r1.subplan_types == r2.subplan_types
            and r1.ranked == r2.ranked)
    if same and (r1.ranked or r2.ranked):
        same = (np.array_equal(r1.doc_ids, r2.doc_ids)
                and np.array_equal(r1.doc_scores, r2.doc_scores)
                and np.array_equal(r1.anchor_scores, r2.anchor_scores))
    return same


def oracle_agrees(w, req, resp) -> bool:
    """`resp` against the O(corpus) oracles of core/engine.py (ranked scores
    to the repo's float32-vs-float64 tolerance of 1e-4)."""
    from repro.core import (MODE_KWORD, brute_force_kword, brute_force_ranked,
                            brute_force_search)
    corpus, index = w["corpus"], w["index"]
    q = list(req.surface_ids)
    if req.mode == MODE_KWORD:
        truth_pos, truth_doc = brute_force_kword(corpus, index, q, req.window)
        if resp.doc_only:
            return not truth_pos and set(resp.doc.tolist()) == truth_doc
        return set(zip(resp.doc.tolist(), resp.pos.tolist())) == truth_pos
    if req.rank:
        a_sc, d_sc, d_lvl = brute_force_ranked(corpus, index, q,
                                               mode=req.mode,
                                               window=req.window)
        if resp.doc_only:
            return set(resp.doc.tolist()) == d_lvl
        got = dict(zip(zip(resp.doc.tolist(), resp.pos.tolist()),
                       resp.anchor_scores.tolist()))
        if set(got) != set(a_sc):
            return False
        close = (lambda x, y: abs(x - y) <= 1e-4 * max(1.0, abs(y)))
        docs = dict(zip(resp.doc_ids.tolist(), resp.doc_scores.tolist()))
        return (all(close(got[k], a_sc[k]) for k in got)
                and all(close(docs[d], d_sc[d]) for d in docs))
    positional, doc_level = brute_force_search(corpus, index, q,
                                               mode=req.mode,
                                               window=req.window)
    if resp.doc_only:
        return set(resp.doc.tolist()) == doc_level
    return set(zip(resp.doc.tolist(), resp.pos.tolist())) == positional


def make_requests(w, seed: int, n: int) -> dict:
    """The smoke's traffic, by kind: the paper's phrase / every-other-word
    near procedure (2n), ranked near with top_k (n), and stop-heavy K-word
    sets (n, ~10% of them with windows wide enough to go to the flex
    executor; n/2 more ranked)."""
    from benchmarks.common import kword_query_stream, paper_query_stream
    from repro.core import MODE_KWORD, MODE_NEAR, SearchRequest
    paper = paper_query_stream(w["corpus"], 2 * n, seed=seed + 1)
    ranked = paper_query_stream(w["corpus"], 2 * n, seed=seed + 2)
    kword = kword_query_stream(w, n + n // 2, seed=seed + 3)
    return {
        "phrase+near": [SearchRequest(q, mode=m) for q, m, _ in paper],
        "ranked near": [SearchRequest(q, mode=MODE_NEAR, rank=True, top_k=10)
                        for q, m, _ in ranked if m == MODE_NEAR],
        "kword": [SearchRequest(q, mode=MODE_KWORD, window=win)
                  for q, win, _ in kword[:n]],
        "ranked kword": [SearchRequest(q, mode=MODE_KWORD, window=win,
                                       rank=True)
                         for q, win, _ in kword[n:]],
    }


def batches(reqs: list, size: int = BATCH):
    for i in range(0, len(reqs), size):
        yield reqs[i:i + size]


def precompile(engines, reqs: list) -> int:
    """Compile, concurrently, every bucket step the engines' batched
    executors run for `reqs` in BATCH-sized batches.  XLA compiles outside
    the GIL and each step takes seconds to compile for the chip, so a cold
    run compiles them a CPU's worth at a time instead of one by one."""
    from concurrent.futures import ThreadPoolExecutor
    steps = {}
    for eng in engines:
        be = eng.batch_executor
        for chunk in batches(reqs):
            for key, low in be.lower_steps(
                    [eng.plan_request(r) for r in chunk], chunk).items():
                steps[(be.impl, key)] = low
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        list(pool.map(lambda low: low.compile(), steps.values()))
    return len(steps)


def device_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("bytes_in_use", "not reported"))


def build_world(args):
    from benchmarks.common import bench_world
    tiny = args.cpu_rehearsal
    t0 = time.perf_counter()
    w = bench_world(n_docs=40 if tiny else 1200,
                    mean_doc_len=200.0 if tiny else 800.0, seed=args.seed,
                    stop_mass=0.4)
    _log(f"[smoke timing] world build: {time.perf_counter() - t0:.3f} s "
         f"({w['corpus'].n_docs} docs, {w['corpus'].n_tokens} tokens)")
    return w


def serve_config(impl: str | None):
    from repro.serve.search_serve import SearchServeConfig
    return SearchServeConfig(queries=BATCH, postings_pad=4096, seed_pad=1024,
                             n_basic=1, n_expanded=1, n_stop=1, n_first=1,
                             n_multi=1, impl=impl)


def serve_all(serve, reqs: list) -> list:
    return [r for chunk in batches(reqs) for r in serve.search_batch(chunk)]


def one_chip(args, jax, check: Checks, impl: str | None) -> None:
    """The main path on one chip (see the module docstring)."""
    from repro.core import AdditionalIndexEngine
    from repro.core.api import STATUS_SERVED_EXACT
    from repro.core.kword import KW_DEVICE_MAX_WINDOW
    from repro.launch.mesh import make_host_mesh
    from repro.serve import FrontDoor, FrontDoorConfig
    from repro.serve.search_serve import SearchServe

    tiny = args.cpu_rehearsal
    w = build_world(args)
    index = w["index"]
    # a long batch window makes every micro-batch BATCH requests in submit
    # order, the batches `precompile` compiled for; deadlines and the shard
    # timeout leave room for compiles, and the cache is off so that every
    # pass runs on the device
    cfg = FrontDoorConfig(max_batch=BATCH, batch_window_ms=500.0,
                          default_deadline_ms=3.6e6, cache_capacity=0,
                          shard_timeout_s=1800.0)
    front = FrontDoor(index, cfg=cfg, batch_impl=impl)
    engine = front.backends[0].engine
    be = engine.batch_executor
    _log(f"front door backend: impl={be.impl} interpret={be.interpret}")
    if not tiny:
        check(be.impl == "pallas" and not be.interpret,
              "the front door's default backend is compiled Pallas")
    jax.block_until_ready(be.dev.device_arena)
    _log(f"device arena bytes: {be.dev.device_nbytes()}; device 0 "
         f"bytes_in_use after load: {device_bytes(jax.devices()[0])}")

    groups = make_requests(w, args.seed, 16 if tiny else 64)
    reqs = [r for rs in groups.values() for r in rs]
    ref_engine = AdditionalIndexEngine(index, batch_impl="ref")
    t0 = time.perf_counter()
    n = precompile([engine, ref_engine], reqs)
    _log(f"[smoke timing] precompile: {n} bucket steps (front door and ref "
         f"engine) in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    got = front.search_batch(reqs)
    _log(f"[smoke timing] front door first pass: "
         f"{time.perf_counter() - t0:.3f} s for {len(reqs)} requests")
    t0 = time.perf_counter()
    got = front.search_batch(reqs)
    _log(f"[smoke timing] front door warm pass: "
         f"{time.perf_counter() - t0:.3f} s for {len(reqs)} requests")
    st = front.stats
    front.close()
    t0 = time.perf_counter()
    want = [r for chunk in batches(reqs)
            for r in ref_engine.search_batch(chunk)]
    _log(f"[smoke timing] ref engine pass: {time.perf_counter() - t0:.3f} s")

    lo = 0
    for kind, rs in groups.items():
        g, wnt = got[lo:lo + len(rs)], want[lo:lo + len(rs)]
        lo += len(rs)
        exact = sum(r.status == STATUS_SERVED_EXACT for r in g)
        bad = sum(not same_response(a, b) for a, b in zip(g, wnt))
        wide = sum(r.window is not None and r.window > KW_DEVICE_MAX_WINDOW
                   for r in rs)
        _log(f"front door {kind}: {len(rs)} requests, {exact} SERVED_EXACT, "
             f"{bad} mismatches vs ref engine"
             + (f", {wide} wide-window (flex)" if "kword" in kind else ""))
        check(exact == len(rs), f"{kind}: non-EXACT responses")
        check(bad == 0, f"{kind}: front door != ref engine")
    _log(f"front door stats: exact {st.served_exact}, degraded "
         f"{st.served_degraded}, shed {st.shed} {st.shed_reasons}, "
         f"flex routed {st.flex_routed}")
    check(len(reqs) >= (64 if tiny else 256), "too few requests")

    serve = SearchServe(index, serve_config(impl),
                        make_host_mesh(data=1, model=1))
    t0 = time.perf_counter()
    sgot = serve_all(serve, reqs)
    bad = sum(not same_response(a, b) for a, b in zip(sgot, want))
    _log(f"SearchServe (1 device): {len(sgot)} requests, {bad} mismatches "
         f"vs ref engine [smoke timing: {time.perf_counter() - t0:.3f} s, "
         f"compiles included]")
    check(bad == 0, "SearchServe != ref engine")

    n_oracle = 24 if tiny else 64
    picks, lo = [], 0
    for rs in groups.values():            # the first requests of each kind
        picks += range(lo, lo + min(n_oracle // len(groups), len(rs)))
        lo += len(rs)
    t0 = time.perf_counter()
    bad = sum(not oracle_agrees(w, reqs[i], got[i]) for i in picks)
    _log(f"oracle checks: {len(picks)} responses, {bad} mismatches "
         f"[smoke timing: {time.perf_counter() - t0:.3f} s]")
    check(bad == 0, "front door != brute-force oracle")
    check(len(picks) >= n_oracle, "too few oracle checks")

    # the compiled program of one served bucket step per kind: the first
    # request of the kind that the batch executor runs (not flex)
    kinds = {"phrase": [r for r in groups["phrase+near"]
                        if r.mode == "phrase"],
             "ranked": groups["ranked near"], "kword": groups["kword"]}
    for kind, cands in kinds.items():
        steps = next((list(s.values()) for s in (
            be.lower_steps([engine.plan_request(r)], [r]) for r in cands)
            if s), [])
        n_calls = [s.compile().as_text().count("tpu_custom_call")
                   for s in steps]
        _log(f"{kind} bucket steps: {len(steps)}, tpu_custom_call per "
             f"compiled step: {n_calls}")
        if not tiny:
            check(bool(steps) and all(n > 0 for n in n_calls),
                  f"{kind}: compiled step has no Pallas kernel")


def four_chips(args, jax, check: Checks, impl: str | None) -> None:
    """SearchServe doc-partitioned over a 4-chip mesh vs the one-chip
    engine, bit for bit (ranked pmin/pmax merge included), on one batch of
    each request kind."""
    from repro.core import AdditionalIndexEngine
    from repro.launch.mesh import make_host_mesh
    from repro.serve.search_serve import SearchServe

    w = build_world(args)
    index = w["index"]
    groups = make_requests(w, args.seed, 8 if args.cpu_rehearsal else 16)
    reqs = [r for rs in groups.values() for r in rs]

    serve = SearchServe(index, serve_config(impl), make_host_mesh(data=4))
    _log(f"SearchServe: {serve.n_dp} doc shards x "
         f"{serve.executor.docs_per_dp} docs")
    t0 = time.perf_counter()
    sgot = serve_all(serve, reqs)
    _log(f"[smoke timing] 4-chip serve pass: {time.perf_counter() - t0:.3f} s"
         f" for {len(reqs)} requests (compiles included)")
    for d in jax.devices():
        _log(f"device {d.id} ({d.device_kind}) bytes_in_use: "
             f"{device_bytes(d)}")

    engine = AdditionalIndexEngine(index, batch_impl=impl)
    t0 = time.perf_counter()
    n = precompile([engine], reqs)
    want = [r for chunk in batches(reqs) for r in engine.search_batch(chunk)]
    _log(f"one-chip engine: impl={engine.batch_executor.impl} "
         f"interpret={engine.batch_executor.interpret} [smoke timing: "
         f"{time.perf_counter() - t0:.3f} s, {n} precompiled steps]")
    lo = 0
    for kind, rs in groups.items():
        bad = sum(not same_response(a, b) for a, b in
                  zip(sgot[lo:lo + len(rs)], want[lo:lo + len(rs)]))
        lo += len(rs)
        _log(f"4-chip SearchServe {kind}: {len(rs)} requests, {bad} "
             f"mismatches vs one-chip engine")
        check(bad == 0, f"{kind}: 4-chip serve != one-chip engine")


def main() -> None:
    args = _args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import jax
        import repro  # noqa: F401
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        _die(f"the repo's sources are not next to this script: {e}")
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        _die(f"JAX finds no TPU (platform {platform!r})")
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        _die(f"{need} devices needed, JAX finds {len(devs)}")
    if not args.cpu_rehearsal:
        _log(f"compile cache: {enable_compile_cache()}")
    _log(f"devices: {len(devs)} x {platform}/{devs[0].device_kind}")
    # the rehearsal runs the Pallas kernels in interpret mode; on a chip
    # every entry point keeps its platform default
    impl = "pallas" if args.cpu_rehearsal else None

    check = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args, jax, check, impl)
    else:
        one_chip(args, jax, check, impl)
    _log(f"[smoke timing] total: {time.perf_counter() - t0:.3f} s; "
         f"failed checks: {check.failed}")
    if check.failed:
        _die(f"{check.failed} check(s) failed")
    result = {"ok": True, "device": {"platform": platform,
                                     "kind": devs[0].device_kind,
                                     "count": len(devs)}}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
