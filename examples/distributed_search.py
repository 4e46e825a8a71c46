"""Distributed search demo: one corpus document-partitioned over every
device, served through the unified shard_map'd serve tier (each device holds
only its own slice of the posting arena and executes only its own rows),
verified bit-identical against the in-process engine; plus a ring
all-reduce demo.

On an accelerator host it shards over the devices that are there (four on a
v5e 2x2).  On a CPU-only host it re-execs itself once with XLA_FLAGS for 8
virtual host devices:

    PYTHONPATH=src python examples/distributed_search.py
"""
import os
import sys

import jax

if (jax.default_backend() == "cpu" and "host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax.numpy as jnp                                           # noqa: E402
import numpy as np                                                # noqa: E402

from repro.core import (AdditionalIndexEngine, CorpusConfig,      # noqa: E402
                        LexiconConfig, MODE_NEAR, SearchRequest, build_all,
                        generate_corpus, make_lexicon_and_analyzer)
from repro.dist.collectives import make_ring_all_reduce           # noqa: E402
from repro.launch.mesh import make_host_mesh                      # noqa: E402
from repro.serve.search_serve import (SearchServe,                # noqa: E402
                                      SearchServeConfig)


def main():
    n = len(jax.devices())
    print(f"devices: {n} x {jax.devices()[0].device_kind}")
    mesh = make_host_mesh(data=n)

    # ONE corpus, documents partitioned over the dp shards by the serve
    # tier itself (contiguous doc ranges; each shard's arena holds only its
    # own postings)
    lex_cfg = LexiconConfig(n_surface=8000, n_base=6000, n_stop=200,
                            n_frequent=600, seed=0)
    lex, ana = make_lexicon_and_analyzer(lex_cfg)
    corpus = generate_corpus(lex_cfg, CorpusConfig(n_docs=320, seed=0))
    index = build_all(corpus, lex, ana)
    engine = AdditionalIndexEngine(index)

    cfg = SearchServeConfig(queries=8, postings_pad=2048, seed_pad=512,
                            n_basic=1, n_expanded=1, n_stop=1, n_first=1,
                            n_multi=1)
    serve = SearchServe(index, cfg, mesh)
    print(f"document-sharded serve: {serve.n_dp} shards x "
          f"{serve.executor.docs_per_dp} docs")

    rng = np.random.default_rng(0)
    requests = []
    while len(requests) < cfg.queries:
        d = int(rng.integers(corpus.n_docs))
        toks = corpus.doc(d)
        if len(toks) < 10:
            continue
        st = int(rng.integers(len(toks) - 6))
        requests.append(SearchRequest(toks[st:st + 3].tolist()))

    got = serve.search_batch(requests)
    want = engine.search_batch(requests)
    assert all(np.array_equal(w.doc, g.doc) and np.array_equal(w.pos, g.pos)
               for w, g in zip(want, got))
    print(f"serve over {n} shards == engine: counts={[len(r.doc) for r in got]}")

    # ranked across the document shards: per-shard scores merge through the
    # same psum/pmax step and stay bit-identical to the engine
    ranked_reqs = [SearchRequest(r.surface_ids, mode=MODE_NEAR, rank=True,
                                 top_k=3) for r in requests]
    rs, re_ = serve.search_batch(ranked_reqs), engine.search_batch(ranked_reqs)
    assert all(np.array_equal(w.doc_ids, g.doc_ids)
               and np.array_equal(w.doc_scores, g.doc_scores)
               for w, g in zip(re_, rs))
    print(f"ranked serve over {n} shards == engine: "
          f"top docs {[r.doc_ids[:2].tolist() for r in rs[:4]]}")

    ring = make_ring_all_reduce(mesh, "data")
    X = jnp.asarray(np.random.default_rng(0).normal(size=(n, 32)).astype(np.float32))
    from jax.sharding import NamedSharding, PartitionSpec as P
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    with mesh:
        red = jax.jit(ring)(Xs)
    print(f"ring all-reduce max err: "
          f"{float(jnp.abs(red - X.sum(0)[None]).max()):.2e}")


if __name__ == "__main__":
    main()
