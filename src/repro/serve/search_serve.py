"""Production batched phrase-query serving over a document-sharded index.

This tier runs the SAME execution engine as the in-process engines: plans
are tensorized into the batch-executor row tables (core/fetch_tables.py,
core/batch_executor.py) — full subplan unions, all lemma forms, doc-only
fallbacks, near-stop checks — and executed with the same `bucket_step_math`
the engine jit's, wrapped in shard_map over document shards.  The old
serve-only single-subplan executor (first subplan, primary form per group)
is gone; serve results are bit-identical to `engine.search_batch`.

Distributed-IR layout: documents are partitioned contiguously over the
dp = pod x data mesh axes; every dp shard holds only its own slice of the
posting arena (all six streams concatenated so a fetch is a single gather —
re-packed per shard into the bit-packed block store of core/postings.py, so
each device holds packed lanes + per-block anchor/width metadata instead of
raw int32 columns) plus the matching near-stop rows.  Host-side
tensorization is shard-segmented (batch_executor._build_rows): each
execution row targets exactly one doc shard, so a row's fetches live wholly
inside one dp shard's arena and carry an `owner` column.  Inside shard_map every device executes only
its own rows (others are masked inactive), and the per-row results — each
produced on exactly one device — are combined with a single `psum` over the
dp axes (the owner's key + 1, zero elsewhere).  The `model` axis replicates
the index and serves to scale query throughput (the launcher round-robins
query batches over it).

Per-row work is O(the row's own postings): no device ever re-sorts another
shard's slab, so adding doc shards adds rows (capacity) without inflating
per-shard step cost.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core.api import SearchRequest, SearchResponse, as_request
from repro.core.batch_executor import P_FLOOR, BatchExecutor, bucket_step_math
from repro.core.builder import IndexSet
from repro.core.engine import _coerce_requests
from repro.core.executor import SENTINEL, _next_pow2
from repro.core.fetch_tables import batch_table_specs
from repro.core.kword import MODE_KWORD
from repro.core.planner import MODE_PHRASE, Planner
from repro.kernels.ops import resolve_kernels

__all__ = ["SearchServeConfig", "SearchServe", "arena_specs",
           "query_table_specs", "make_search_serve_step"]


@dataclasses.dataclass(frozen=True)
class SearchServeConfig:
    name: str = "veretennikov-serve"
    # groups/fetch_slots/postings_pad/seed_pad are CAPS: they size the
    # dry-run cells and bound tensorization, but live steps run through a
    # <=3-tier (G, F, P0, P) ladder derived from the first batch's actual
    # row population (plus pow2-tight T), so a smoke-scale workload is not
    # billed for the full production slab
    queries: int = 64              # query batch size (sizing hint for rows)
    rows: int = 0                  # T cap: execution rows per step; 0 = 2*queries
    groups: int = 8                # G cap: fetch groups per row (seed + G-1)
    fetch_slots: int = 8           # F cap: union slots per group (forms + splits)
    postings_pad: int = 32768      # P cap: padded postings per constraint slot
    seed_pad: int = 0              # P0: seed (pivot) slot pad; 0 = postings_pad.
                                   # The planner seeds with the RAREST list,
                                   # so a small pad bounds the seed gather +
                                   # membership searches (§Perf)
    check_slots: int = 4           # C: near-stop checks on the pivot group
    check_forms: int = 2           # M: stop forms per near-stop check
    ns_k: int = 20                 # stream-3 slots per posting
    # per-shard arena sizes (basic|expanded|stop|first|multi segments
    # concatenated), in POSTINGS — the packed block store derives its block
    # count from this and its lane-word budget from `lane_words`
    n_basic: int = 10_000_000
    n_expanded: int = 17_000_000
    n_stop: int = 23_000_000
    n_first: int = 4_000_000
    n_multi: int = 12_000_000      # multi-component key postings (pairs+triples)
    lane_words: int = 0            # int32 words of packed posting deltas per
                                   # shard; 0 = n_arena (a ~32-bit/posting
                                   # budget — generous: doc/pos/dist widths
                                   # at bench scale average well under that)
    impl: str | None = None        # kernels: ref | pallas; None = platform's
                                   # choice (ops.resolve_kernels)
    interpret: bool | None = None  # pallas interpreter; None = off on a TPU
    ranked: bool = False           # dry-run cells: lower the proximity-scored
                                   # step variant (serving always compiles
                                   # both lazily as ranked requests arrive)

    @property
    def n_arena(self) -> int:
        return (self.n_basic + self.n_expanded + self.n_stop + self.n_first
                + self.n_multi)

    @property
    def n_blocks(self) -> int:
        """Packed blocks per shard (BLOCK postings each)."""
        from repro.core.postings import BLOCK
        return max(1, -(-self.n_arena // BLOCK))

    @property
    def n_lane_words(self) -> int:
        return self.lane_words or self.n_arena

    @property
    def p_seed(self) -> int:
        return self.seed_pad or self.postings_pad

    @property
    def task_rows(self) -> int:
        return self.rows or 2 * self.queries


def _dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _dp_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in _dp_axes(mesh))


def arena_specs(cfg: SearchServeConfig, n_shards: int) -> dict:
    """ShapeDtypeStructs for the stacked per-shard index arenas: the packed
    block store (lanes + per-block base/width/anchor metadata, see
    core/postings.PackedPostings) plus the raw stream-3 near-stop slots."""
    i32 = jnp.int32
    nb = cfg.n_blocks
    return {
        "lanes": jax.ShapeDtypeStruct((n_shards, cfg.n_lane_words), i32),
        "blk_meta": jax.ShapeDtypeStruct((n_shards, nb, 5), i32),
        "basic_ns": jax.ShapeDtypeStruct((n_shards, cfg.n_basic, cfg.ns_k),
                                         jnp.int16),
    }


def query_table_specs(cfg: SearchServeConfig) -> dict:
    """ShapeDtypeStructs for one serve row batch (replicated to every shard):
    the batch-executor schema plus the per-row `owner` column."""
    return batch_table_specs(cfg.task_rows, cfg.groups, cfg.fetch_slots,
                             cfg.check_slots, cfg.check_forms, owner=True)


# ---------------------------------------------------------------------------
# the serve step: shard_map'd bucket math + one psum merge
# ---------------------------------------------------------------------------


def make_search_serve_step(cfg: SearchServeConfig, mesh,
                           ranked: bool | None = None,
                           p_seed: int | None = None,
                           postings_pad: int | None = None,
                           kword: bool = False):
    """Returns step(arenas, tables) -> (keys [T, F*P0] int64, found bool)
    — plus proximity scores [T, F*P0] float32 when `ranked` (default:
    cfg.ranked), computed by the SAME bucket math the engine jit's and
    merged across shards right after the int64 psum (scores ride a pmax:
    every row is owned by exactly one dp shard, so both collectives are
    pure "take the owner's result").

    arenas: dict of stacked per-shard arrays (leading dim = n_dp shards),
    sharded P(dp); tables: dict per query_table_specs, replicated — each
    row's fetch starts are LOCAL to its owner shard's arena.  Outputs are
    replicated: `keys` holds the seed's global 63-bit keys where `found`,
    SENTINEL elsewhere — exactly what the batch executor's merge consumes.
    """
    if ranked is None:
        ranked = cfg.ranked
    impl, interpret = resolve_kernels(cfg.impl, cfg.interpret)
    dp = _dp_axes(mesh)
    # cfg gives the CAP pads (the dry-run cell shapes); the serve executor's
    # tier ladder lowers tighter variants for the live plan population
    P0 = p_seed or cfg.p_seed
    Pc = postings_pad or cfg.postings_pad

    def local(arenas, t):
        me = jax.lax.axis_index(dp[0])
        for a in dp[1:]:
            me = me * mesh.shape[a] + jax.lax.axis_index(a)
        own = t["owner"] == me
        tt = {k: v for k, v in t.items() if k != "owner"}
        tt["active"] = t["active"] & own[:, None]
        # this shard's packed arena (leading stacked-shard dim is 1 inside
        # shard_map), keyed the way bucket_step_math expects
        arena = {k: v[0] for k, v in arenas.items() if k != "basic_ns"}
        arena["near_stop"] = arenas["basic_ns"][0]
        out = bucket_step_math(
            arena, tt,
            P0=P0, P=Pc, impl=impl, interpret=interpret,
            ranked=ranked, kword=kword)
        if ranked:
            a64, found, scores = out
        else:
            a64, found = out
        # every row is owned by exactly one dp shard, so the merge takes
        # the owner's result: an int64 sum of key + 1 on the owner and 0
        # elsewhere (a TPU all-reduce lowers 64-bit integers only as a sum)
        own_hit = found & own[:, None]
        s = jax.lax.psum(jnp.where(own_hit, a64 + 1, 0), dp)
        hit = s > 0
        a64 = jnp.where(hit, s - 1, SENTINEL)
        if not ranked:
            return a64, hit
        scores = jax.lax.pmax(jnp.where(own_hit, scores, -1.0), dp)
        return a64, hit, jnp.where(hit, scores, 0.0)

    spec_shard = P(dp)
    spec_rep = P()
    a_specs = {k: spec_shard for k in arena_specs(cfg, 1)}
    q_specs = {k: spec_rep for k in query_table_specs(cfg)}
    out_specs = (spec_rep, spec_rep, spec_rep) if ranked \
        else (spec_rep, spec_rep)
    fn = shard_map(local, mesh=mesh, in_specs=(a_specs, q_specs),
                   out_specs=out_specs, check_vma=False)

    def step(arenas: dict, tables: dict):
        return fn(arenas, tables)
    return step


# ---------------------------------------------------------------------------
# host side: doc-partitioned arenas + the serve batch executor
# ---------------------------------------------------------------------------


class _ServeBatchExecutor(BatchExecutor):
    """BatchExecutor whose rows execute through the shard_map'd serve step.

    Inherits tensorization (seed ordering, shard segmentation, long-list
    splitting), flex-escape routing, and the merge tail — overriding only
    the caps (fixed table shapes from cfg) and `_run_rows` (fixed-shape
    chunks through the jit'd distributed step, with fetch starts remapped
    into each owner shard's local arena)."""

    def __init__(self, index: IndexSet, cfg: SearchServeConfig, mesh,
                 docs_per_shard: int | None = None):
        self.cfg = cfg
        self.mesh = mesh
        self.n_dp = _dp_size(mesh)
        super().__init__(index, impl=cfg.impl, interpret=cfg.interpret,
                         docs_per_shard=docs_per_shard)
        # re-grain the segmentation so every doc shard nests inside one dp
        # shard (rows must never straddle a device's arena slice)
        d = self.dev
        dps = min(d.docs_per_shard, max(1, -(-d.n_docs // self.n_dp)))
        d.docs_per_shard = dps
        d.n_shards = max(1, -(-d.n_docs // dps))
        self.shards_per_dp = max(1, -(-d.n_shards // self.n_dp))
        self.docs_per_dp = dps * self.shards_per_dp
        self._build_dp_arenas(index)
        self._tiers: list | None = None
        self._steps = {(False, False, cfg.p_seed, cfg.postings_pad):
                       jax.jit(make_search_serve_step(cfg, mesh,
                                                      ranked=False))}

    def _step_for(self, ranked: bool, p_seed: int | None = None,
                  postings_pad: int | None = None, kword: bool = False):
        cfg = self.cfg
        key = (ranked, kword, p_seed or cfg.p_seed,
               postings_pad or cfg.postings_pad)
        if key not in self._steps:
            self._steps[key] = jax.jit(
                make_search_serve_step(cfg, self.mesh, ranked=ranked,
                                       p_seed=p_seed,
                                       postings_pad=postings_pad,
                                       kword=kword))
        return self._steps[key]

    # -- tier-ladder persistence (warm restarts) ----------------------------

    def dump_tiers(self, path):
        """Write the learned (G, F, P0, P) tier ladder to `path` (JSON) so a
        fresh executor can warm from it instead of re-deriving (and
        re-compiling) from its first live batch.  No-op before the ladder
        exists."""
        import json
        if self._tiers is None:
            return False
        with open(path, "w") as fh:
            json.dump({"tiers": [list(t) for t in self._tiers]}, fh)
        return True

    def load_tiers(self, path) -> bool:
        """Adopt a previously dumped tier ladder.  Shapes are re-clipped to
        THIS config's caps (a ladder learned under larger caps stays valid —
        the caps remain the emergency tier), deduped, and volume-sorted, so a
        stale file can degrade compile warmth but never correctness."""
        import json
        import os
        if not os.path.exists(path):
            return False
        with open(path) as fh:
            state = json.load(fh)
        cfg = self.cfg
        cap = (cfg.groups, cfg.fetch_slots, cfg.p_seed, cfg.postings_pad)
        tiers = []
        for t in state.get("tiers", ()):
            if len(t) != 4 or any(int(x) < 1 for x in t):
                continue
            t = tuple(min(int(x), c) for x, c in zip(t, cap))
            if t not in tiers:
                tiers.append(t)
        if not tiers:
            return False
        self._tiers = sorted(tiers, key=self._tier_volume)
        return True

    def _build_dp_arenas(self, index: IndexSet):
        """Bucket the global arena to its owning dp shard host-side: shard d
        keeps exactly the postings of docs [d*docs_per_dp, (d+1)*docs_per_dp),
        in global order — so every stream stays a contiguous local segment
        and a global fetch slice maps to one local slice per shard.  Each
        shard's selection is re-packed into its own block store (local
        posting ordinals address it, exactly what the remapped fetch starts
        produce); block-pad ordinals of the global arena are excluded from
        the selection so local ordinals stay dense."""
        from repro.core.postings import PackedPostings
        d = self.dev
        doc_np = d.arena_doc_np
        ns_np = d.near_stop_np
        nb = ns_np.shape[0]                      # basic stream length
        own = doc_np // self.docs_per_dp
        self._sel = [np.nonzero(d.arena_real_np & (own == dd))[0]
                     for dd in range(self.n_dp)]
        packs = [PackedPostings.from_columns(
            {"doc": doc_np[sel], "pos": d.arena_pos_np[sel],
             "dist": d.arena_dist_np[sel]}, fields=("doc", "pos", "dist"))
            for sel in self._sel]
        lw_pad = max(max(len(p.lanes) for p in packs), 1)
        nblk_pad = max(max(p.n_blocks for p in packs), 1)
        nb_l = [int(np.searchsorted(s, nb)) for s in self._sel]
        nb_pad = max(max(nb_l, default=0), 1)
        k = ns_np.shape[1]
        lanes_l = np.zeros((self.n_dp, lw_pad), np.int32)
        meta_l = np.zeros((self.n_dp, nblk_pad, 5), np.int32)
        ns_l = np.full((self.n_dp, nb_pad, k), -1, np.int16)
        for dd, (sel, p) in enumerate(zip(self._sel, packs)):
            lanes_l[dd, :len(p.lanes)] = p.lanes
            meta_l[dd, :p.n_blocks] = p.meta_matrix()
            ns_l[dd, :nb_l[dd]] = ns_np[sel[:nb_l[dd]]]
        dp = _dp_axes(self.mesh)
        shard = NamedSharding(self.mesh, P(dp))
        self.arenas = {
            "lanes": jax.device_put(lanes_l, shard),
            "blk_meta": jax.device_put(meta_l, shard),
            "basic_ns": jax.device_put(ns_l, shard),
        }

    def _caps(self):
        cfg = self.cfg
        return (cfg.groups, cfg.fetch_slots, cfg.fetch_slots,
                cfg.p_seed, cfg.postings_pad)

    def _task_fits(self, groups, kword: bool = False) -> bool:
        if not super()._task_fits(groups, kword=kword):
            return False
        # fixed near-stop slots: checks that don't fit can't be truncated
        # (dropping a check loosens type-4 verification) -> flex
        cfg = self.cfg
        for g in groups:
            for f in g.fetches:
                if len(f.stop_checks) > cfg.check_slots:
                    return False
                if any(len(ids) > cfg.check_forms for _, ids in f.stop_checks):
                    return False
        return True

    def _run_rows(self, rows: list):
        # ranked/unranked and kword/pairwise rows run through separate
        # fixed-shape step variants (scoring and the span join are different
        # programs); each keeps the chunking and start-remapping of the base
        # executor
        for ranked in (False, True):
            for kword in (False, True):
                self._run_rows_variant(
                    [r for r in rows if r.task.ranked == ranked
                     and (r.task.mode == MODE_KWORD) == kword],
                    ranked, kword)

    def _row_shape(self, row) -> tuple:
        """Pow2-padded (G, F, P0, P) this row actually needs, clipped to the
        cfg caps (tensorization already guarantees the raw requirements
        fit them)."""
        cfg = self.cfg
        G = max(2, _next_pow2(len(row.groups), floor=2))
        F = _next_pow2(max(len(g.slots) for g in row.groups), floor=1)
        P0 = _next_pow2(max((ln for _, _, ln in row.groups[0].slots),
                            default=1), floor=P_FLOOR)
        Pc = _next_pow2(max((ln for g in row.groups[1:] for _, _, ln in g.slots),
                            default=1), floor=P_FLOOR)
        return (min(G, cfg.groups), min(F, cfg.fetch_slots),
                min(P0, cfg.p_seed), min(Pc, cfg.postings_pad))

    @staticmethod
    def _tier_volume(s: tuple) -> int:
        G, F, P0, Pc = s
        return F * P0 + (G - 1) * F * Pc

    def _tier_ladder(self, rows: list) -> list:
        """Derive <= 3 nested (G, F, P0, P) tiers from the first batch's row
        population (the auto_docs_per_shard move applied to table shapes):
        rows volume-sorted, elementwise max over tertiles, running max keeps
        the ladder monotone.  cfg's slab sizes stay pure CAPS — the dry-run
        cell contract — and serve as the emergency tier for later rows that
        outgrow the population the ladder was derived from."""
        if self._tiers is None:
            shapes = sorted((self._row_shape(r) for r in rows),
                            key=self._tier_volume)
            n = len(shapes)
            tiers, prev = [], (0, 0, 0, 0)
            for third in (shapes[:max(n // 3, 1)],
                          shapes[max(n // 3, 1):max(2 * n // 3, 1)],
                          shapes[max(2 * n // 3, 1):]):
                if not third:
                    continue
                t = tuple(max(prev[i], max(s[i] for s in third))
                          for i in range(4))
                prev = t
                if t not in tiers:
                    tiers.append(t)
            self._tiers = tiers
        return self._tiers

    def _run_rows_variant(self, rows: list, ranked: bool, kword: bool = False):
        if not rows:
            return
        cfg = self.cfg
        cap = (cfg.groups, cfg.fetch_slots, cfg.p_seed, cfg.postings_pad)
        tiers = self._tier_ladder(rows)
        assign: dict = {}
        for row in rows:
            req = self._row_shape(row)
            tier = next((t for t in tiers
                         if all(a <= b for a, b in zip(req, t))), cap)
            assign.setdefault(tier, []).append(row)
        for (G, F, P0, Pc), rs in assign.items():
            step = self._step_for(ranked, p_seed=P0, postings_pad=Pc,
                                  kword=kword)
            for lo in range(0, len(rs), cfg.task_rows):
                part = rs[lo:lo + cfg.task_rows]
                # tight T: pow2-chunked instead of the full fixed slab, so a
                # smoke-sized batch no longer drags task_rows dead rows
                # through the packed unpack + gather + sort
                T = min(cfg.task_rows, _next_pow2(len(part), floor=4))
                t = self._tensorize_bucket(part, G, F, cfg.check_slots,
                                           cfg.check_forms, T)
                owner = np.zeros(T, np.int32)
                owner[:len(part)] = [row.shard // self.shards_per_dp
                                     for row in part]
                # remap global fetch starts into each owner shard's local
                # arena: one vectorized searchsorted per dp shard touched
                live = t["length"] > 0
                for dd in np.unique(owner[:len(part)]):
                    m = (owner == dd)[:, None, None] & live
                    t["start"][m] = np.searchsorted(self._sel[dd],
                                                    t["start"][m])
                t["owner"] = owner
                self._count_slab(part, T, (G, F, P0, Pc))
                tj = {k: jnp.asarray(v) for k, v in t.items()}
                key = self._step_key(tj, dict(ranked=ranked, kword=kword,
                                              P0=P0, P=Pc))
                with self._first_run(key):
                    with self.mesh:
                        out = step(self.arenas, tj)
                    out = [np.asarray(x) for x in out]
                self._scatter_row_keys(part, *out)


class SearchServe:
    """End-to-end distributed serving facade: SearchRequests → plan → serve
    tables → shard_map step → merged SearchResponses, bit-identical to
    `engine.search_batch` — ranked top-k included (the scoring pass is the
    same bucket math, merged right after the cross-shard psum).

    Plans that exceed the fixed table shapes run through the flexible
    executor host-side (the same escape hatch the engine uses)."""

    def __init__(self, index: IndexSet, cfg: SearchServeConfig, mesh,
                 docs_per_shard: int | None = None, occ_counts=None):
        self.index = index
        self.cfg = cfg
        self.mesh = mesh
        # occ_counts: cluster-global occurrence stats when this serve tier
        # holds one doc shard / segment of a larger corpus (see Planner)
        self.planner = Planner(index, occ_counts=occ_counts)
        self.executor = _ServeBatchExecutor(index, cfg, mesh,
                                            docs_per_shard=docs_per_shard)

    @property
    def n_dp(self) -> int:
        return self.executor.n_dp

    def refresh_occ_counts(self, occ_counts=None):
        """Re-snapshot planner pivot statistics (see Planner.refresh_occ_counts)."""
        self.planner.refresh_occ_counts(occ_counts)

    def plan_request(self, request: SearchRequest):
        return self.planner.plan(list(request.surface_ids),
                                 mode=request.mode, window=request.window,
                                 ranked=request.rank)

    def plan(self, surface_ids, mode: str = MODE_PHRASE,
             window: int | None = None, ranked: bool = False):
        """Host-side plan introspection (not a search entry point)."""
        return self.planner.plan(list(surface_ids), mode=mode, window=window,
                                 ranked=ranked)

    def execute_batch(self, plans, requests=None,
                      max_results: int | None = None) -> list[SearchResponse]:
        return self.executor.execute_batch(plans, requests=requests,
                                           max_results=max_results)

    def search(self, request, mode: str = MODE_PHRASE,
               window: int | None = None,
               max_results: int | None = None) -> SearchResponse:
        if not isinstance(request, SearchRequest):
            request = as_request(request, mode, window, max_results,
                                 what="SearchServe.search")
        return self.search_batch([request])[0]

    def search_batch(self, requests, modes: str | list = MODE_PHRASE,
                     window: int | None = None,
                     max_results: int | None = None) -> list[SearchResponse]:
        """A batch of SearchRequests through the distributed step.  The
        positional (queries, modes=...) form is a deprecated shim."""
        requests = list(requests)
        if not all(isinstance(r, SearchRequest) for r in requests):
            requests = _coerce_requests(requests, modes, window, max_results,
                                        what="SearchServe.search_batch")
        plans = [self.plan_request(r) for r in requests]
        return self.execute_batch(plans, requests=requests)
