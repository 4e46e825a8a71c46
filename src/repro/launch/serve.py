"""Serving launcher.

Two modes:
  search — build the paper's indexes over a synthetic corpus and serve a
           query stream.  Default is closed-loop batch timing through the
           tensorized serve step (the same step the dry-run lowers at 512
           chips); passing --qps switches to an OPEN-LOOP Poisson arrival
           process through the serving front door (serve.front.FrontDoor)
           and reports what a latency SLO actually sees — p50/p95/p99 of
           per-request latency under load, plus shed/degraded counts —
           instead of closed-loop us/query (which hides queueing delay
           entirely: a closed loop only offers the next request after the
           previous one finished).
  lm     — greedy decode from a smoke LM with the KV cache serve_step.

    PYTHONPATH=src python -m repro.launch.serve --mode search --queries 32
    PYTHONPATH=src python -m repro.launch.serve --mode search --ranked --top-k 5
    PYTHONPATH=src python -m repro.launch.serve --mode search --qps 50 --duration 5
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch llama3-8b
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache


def _device_label() -> str:
    """platform/device_kind x count, as JAX reports the devices."""
    d = jax.devices()
    return f"{d[0].platform}/{d[0].device_kind} x{len(d)}"


def _search_world(n_queries: int, ranked: bool, top_k: int):
    """The launcher's synthetic serving world: lexicon, corpus, full index
    set, and a repeatable query workload (shared by both loop modes)."""
    from repro.core import (CorpusConfig, LexiconConfig, MODE_NEAR,
                            SearchRequest, build_all, generate_corpus,
                            make_lexicon_and_analyzer)
    lex_cfg = LexiconConfig(n_surface=20_000, n_base=15_000, n_stop=400,
                            n_frequent=1200, seed=0)
    lex, ana = make_lexicon_and_analyzer(lex_cfg)
    corpus = generate_corpus(lex_cfg, CorpusConfig(n_docs=300, seed=0))
    index = build_all(corpus, lex, ana)
    rng = np.random.default_rng(0)
    requests = []
    while len(requests) < n_queries:
        d = int(rng.integers(corpus.n_docs))
        toks = corpus.doc(d)
        if len(toks) < 10:
            continue
        st = int(rng.integers(len(toks) - 6))
        if ranked:
            requests.append(SearchRequest(toks[st:st + 6:2].tolist(),
                                          mode=MODE_NEAR, rank=True,
                                          top_k=top_k))
        else:
            requests.append(SearchRequest(toks[st:st + 3].tolist()))
    return index, requests


def serve_search(n_queries: int, ranked: bool = False, top_k: int = 10):
    from repro.launch.mesh import make_host_mesh
    from repro.serve.search_serve import SearchServe, SearchServeConfig
    index, requests = _search_world(n_queries, ranked, top_k)
    mesh = make_host_mesh(data=1, model=1)
    cfg = SearchServeConfig(queries=n_queries, postings_pad=8192,
                            seed_pad=2048, n_basic=1, n_expanded=1,
                            n_stop=1, n_first=1, n_multi=1)
    serve = SearchServe(index, cfg, mesh)
    results = serve.search_batch(requests)   # warm
    t0 = time.perf_counter()
    results = serve.search_batch(requests)
    dt = time.perf_counter() - t0
    label = "ranked top-%d" % top_k if ranked else "phrase"
    print(f"[serve/search] {n_queries} {label} queries in {dt*1e3:.1f} ms "
          f"({dt/n_queries*1e6:.0f} us/query, {_device_label()}, "
          f"{serve.n_dp} doc shard(s)); "
          f"hit counts: {[len(r.doc) for r in results[:8]]}...")
    if ranked:
        r = next((r for r in results if r.doc_ids is not None
                  and len(r.doc_ids)), None)
        if r is not None:
            print(f"[serve/search] sample ranking: "
                  f"{[(h.doc, round(h.score, 3)) for h in r.hits[:5]]}")


def serve_search_open_loop(qps: float, duration: float, deadline_ms: float,
                           ranked: bool = False, top_k: int = 10,
                           n_queries: int = 64):
    """Open-loop load: Poisson arrivals at `qps` through the front door for
    `duration` seconds.  Unlike the closed loop above, arrivals do NOT wait
    for completions, so queueing delay is measured, not hidden — the
    latencies reported here are what a client-side SLO would see."""
    import dataclasses as _dc

    from repro.serve import FrontDoor, FrontDoorConfig
    index, requests = _search_world(n_queries, ranked, top_k)
    cfg = FrontDoorConfig(default_deadline_ms=deadline_ms, cache_capacity=0,
                          shard_timeout_s=max(60.0, 4 * deadline_ms / 1000.0))
    front = FrontDoor(index, cfg=cfg)
    # warm the jit caches outside the measured window (generous deadline).
    # Open-loop micro-batches come in many sizes, and the serve executor
    # pow2-buckets its task rows — ramp the warm batches so every chunk
    # shape the measured window can hit is already compiled.
    warm = [_dc.replace(r, deadline_ms=600_000.0) for r in requests]
    n = 1
    while n < len(warm):
        front.search_batch(warm[:n])
        n *= 2
    front.search_batch(warm)
    front.stats = type(front.stats)()
    gcs0, gc_s0 = obs.GC.collections, obs.GC.pause_s

    rng = np.random.default_rng(1)
    tickets = []
    t0 = time.monotonic()
    t_end = t0 + duration
    i = 0
    while time.monotonic() < t_end:
        tickets.append(front.submit(requests[i % len(requests)]))
        i += 1
        time.sleep(rng.exponential(1.0 / qps))
    resps = [t.result() for t in tickets]
    elapsed = time.monotonic() - t0
    front.close()
    lat = np.array([r.latency_ms for r in resps])
    p50, p95, p99 = np.percentile(lat, [50, 95, 99])
    st = front.stats
    label = "ranked top-%d" % top_k if ranked else "phrase"
    print(f"[serve/search] open-loop {label}: offered "
          f"{len(resps) / elapsed:.1f} qps for {elapsed:.1f} s "
          f"({len(resps)} requests, deadline {deadline_ms:.0f} ms): "
          f"p50 {p50:.1f} ms, p95 {p95:.1f} ms, p99 {p99:.1f} ms; "
          f"exact {st.served_exact}, degraded {st.served_degraded}, "
          f"shed {st.shed} (shed_rate {st.shed_rate:.3f}); queue wait "
          f"{1e3 * st.queue_wait_s / max(st.dequeued, 1):.1f} ms mean; "
          f"{obs.GC.collections - gcs0} gc pauses, "
          f"{1e3 * (obs.GC.pause_s - gc_s0):.1f} ms")


def serve_lm(arch: str, n_tokens: int):
    from repro.models import transformer as tfm
    cfg = get_arch(arch).make_smoke_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    B, S_max = 2, 128
    cache = tfm.init_cache(cfg, B, S_max)
    tok = jnp.zeros((B,), jnp.int32)
    step = jax.jit(lambda p, c, t, i: tfm.decode_step(cfg, p, c, t, i))
    t0 = time.perf_counter()
    out = []
    for i in range(n_tokens):
        logits, cache = step(params, cache, tok, jnp.int32(i))
        tok = jnp.argmax(logits[:, : cfg.vocab], axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    dt = time.perf_counter() - t0
    print(f"[serve/lm] {arch} decoded {n_tokens} tokens x batch {B} in "
          f"{dt*1e3:.0f} ms ({dt/n_tokens*1e3:.1f} ms/token, "
          f"{_device_label()} smoke); first 10: {out[:10]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["search", "lm"], default="search")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--ranked", action="store_true",
                    help="near-mode queries with proximity ranking")
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop Poisson arrival rate through the front "
                         "door (0 = closed-loop batch timing)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop measurement window, seconds")
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="open-loop per-request deadline")
    args = ap.parse_args()
    enable_compile_cache()
    obs.trace_gc()
    if args.mode == "search":
        if args.qps > 0:
            serve_search_open_loop(args.qps, args.duration, args.deadline_ms,
                                   ranked=args.ranked, top_k=args.top_k,
                                   n_queries=args.queries)
        else:
            serve_search(args.queries, ranked=args.ranked, top_k=args.top_k)
    else:
        serve_lm(args.arch, args.tokens)


if __name__ == "__main__":
    main()
