"""Batched plan-compiled execution: a whole query batch in one jit'd call.

The flexible `Executor` (executor.py) walks plans in Python — one device
dispatch per fetch group, one host↔device round-trip per query.  That is
correct but leaves the paper's order-of-magnitude win on the table at serving
time.  This module makes batched search the first-class engine path — and it
is the SINGLE execution engine: the distributed serve tier
(serve/search_serve.py) consumes the same tables and the same bucket math
under shard_map.

1. **Tensorize + segment** — every supported subplan of every query becomes
   one or more *rows* of fixed-shape fetch tables (schema in
   core/fetch_tables.py): `start/length/offset/req_dist/max_abs : [T, G, F]`,
   `band/active : [T, G]`, `shard_base : [T]`, near-stop checks `[T, C, M]`.
   Group 0 is the seed (the near-stop-checked pivot when present, else the
   smallest band-0 group — the same seed rule as the flexible executor);
   groups 1..G-1 constrain it.  F fetch slots per group carry unions over
   morphological forms / expanded orientations / stop-phrase parts.

   *Shard-segmented gather*: posting slices are split host-side at doc-shard
   boundaries (the arena is (doc, pos)-sorted per fetch, so a shard's rows
   are one `searchsorted` away), one row per (task, doc shard) — so each row
   gathers and intersects only its own shard's postings and the whole batch
   does O(arena) work total, instead of re-basing and re-sorting the full
   slab once per shard.  Posting lists longer than P_CAP are split across
   additional F slots of the same group (a union — exactly the semantics F
   already has), which lifts the old 32k-postings-per-fetch cap.

2. **Execute** — one jit'd call per shape bucket: gather from a unified
   posting arena (basic | expanded | stop | first | ordinary | multi
   concatenated block-aligned, so a fetch is a single dynamic-slice of
   posting ordinals) → vectorized unpack of the bit-packed block store
   (core/postings.PackedPostings lanes + per-block anchor/width metadata,
   ops.unpack_postings — ref math or the Pallas unpack kernel) → global
   63-bit key construction → per-row int32 re-basing against the row's
   `shard_base` (`(doc - base) << 17 | pos'` — TPU vector units have no
   int64 lanes) → k-way banded intersection via `ops.banded_intersect_rows`
   (Pallas kernel with per-row dynamic bands, or the `searchsorted` ref
   path).  Near-stop (type 4) checks mask the seed's keys in the same call.

3. **Merge** — host-side, mirroring `Executor.execute` exactly: row keys are
   unioned per task, task results per query; a subplan with no positional
   hits falls back to its distance-disregarding doc-only task (paper step 3),
   with fallback postings counted only when triggered.

Shape discipline: rows are bucketed by (G, F, P, C, M) with `_next_pow2`
padding on every axis and chunked to a gather budget, so the jit compile
cache stays small while padding waste stays bounded.  Queries that exceed
the table caps (> G_CAP groups, > F_CAP unioned forms, splits overflowing
F_SPLIT_CAP slots) or an index whose positions overflow the 17-bit packed
domain fall back to the flexible executor per plan — identical results,
just not batched.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.api import SearchRequest
from repro.core.builder import IndexSet
from repro.core.executor import (SENTINEL, Executor, SearchResult,
                                 _next_pow2, merge_subplan_results,
                                 order_groups_seed_first, proximity_w,
                                 scored_probe)
from repro.core.fetch_tables import (DOCS_PER_SHARD, NO_DIST,
                                     SCORE_DELTA_BITS, TABLE_POS_BITS,
                                     alloc_batch_tables, pack_ns_checks)
from repro.core.kword import KW_DEVICE_MAX_WINDOW, MODE_KWORD
from repro.core.planner import MODE_PHRASE, QueryPlan
from repro.core.postings import (BLOCK, PHRASE_BIAS, POS_BITS, concat_packed,
                                 pad_block_multiple)
from repro.kernels.ops import (I32_SENTINEL, banded_delta_mask_rows,
                               banded_intersect_rows, banded_min_delta_rows,
                               kword_window_hits, packed_layout,
                               resolve_kernels, unpack_postings)

# table caps: a task exceeding these routes its whole plan to the flexible
# executor (rare: >8 AND-groups or >8 unioned form fetches per slot).
# Fetches longer than P_CAP no longer escape: they are split across extra
# F slots (up to F_SPLIT_CAP per group) by the segmented-gather tensorizer.
G_CAP = 8
F_CAP = 8
F_SPLIT_CAP = 64
P_CAP = 1 << 15
P_FLOOR = 128
GATHER_BUDGET = 1 << 23        # max T*G*F*P elements per jit'd gather


def ensure_packed_streams(index: IndexSet) -> dict:
    """The six per-stream packed stores, packing any the builder didn't
    (hand-assembled IndexSets in tests).  "multi" is the pairs-then-triples
    concatenation, matching MultiKeyIndex.arena_columns ordinals."""
    from repro.core.builder import _pack_stream
    b, mk = index.basic, index.multi_key
    if index.ordinary_packed is None:
        index.ordinary_packed = _pack_stream(index.ordinary)
    if b.packed_occ is None:
        b.packed_occ = _pack_stream(b.occurrences)
        b.packed_first = _pack_stream(b.first_occ)
    if index.expanded.packed is None:
        index.expanded.packed = _pack_stream(index.expanded.pairs)
    if index.stop_phrase.packed is None:
        index.stop_phrase.packed = _pack_stream(index.stop_phrase.phrases)
    if mk.packed_pairs is None:
        mk.packed_pairs = _pack_stream(mk.pairs)
        mk.packed_triples = _pack_stream(mk.triples)
    return {
        "basic": b.packed_occ,
        "expanded": index.expanded.packed,
        "stop": index.stop_phrase.packed,
        "first": b.packed_first,
        "ordinary": index.ordinary_packed,
        "multi": concat_packed([mk.packed_pairs, mk.packed_triples]),
    }


class BatchDeviceIndex:
    """All six posting streams concatenated into one device arena — since
    the packed-store refactor, a bit-packed block arena: `lanes` (int32
    packed deltas) plus the `blk_meta` [NB, 5] per-block metadata matrix
    (base lane word, packed widths, per-field anchors), decoded on device
    by ops.unpack_postings.  Each
    stream is padded to a BLOCK multiple so stream bases stay block-aligned;
    the raw `arena_*_np` columns are kept host-side only (shard segmentation
    + serve bucketing + build stats) and never shipped.

    `docs_per_shard` sets the doc-shard granularity of the segmented gather
    (≤ fetch_tables.DOCS_PER_SHARD so packed int32 keys can't overflow);
    smaller shards only add rows, never change results.

    `doc_base` is the index's first GLOBAL doc id (0 for a standalone
    index).  A segment built from a corpus slice (core/segments.py) stores
    LOCAL doc ids in its arenas, but its execution rows are laid on the
    GLOBAL shard grid: row shard ids are global, and each row's
    `shard_base` is the local re-basing origin `shard*dps - doc_base` (may
    be negative), so the rebased int32 keys stay in [0, dps) exactly as for
    an unsegmented index.  Output keys are unaffected (still local doc
    ids); only the row cuts move — and smaller/shifted shards never change
    results.
    """

    def __init__(self, index: IndexSet, docs_per_shard: int | None = None,
                 doc_base: int = 0):
        packed = ensure_packed_streams(index)
        b = index.basic.occurrences
        e = index.expanded.pairs
        s = index.stop_phrase.phrases
        f = index.basic.first_occ
        m = index.multi_key.arena_columns()
        o = index.ordinary

        docs, poss, dists, reals = [], [], [], []
        self.bases = {}
        off = 0
        for name, doc, pos, dist in (
                ("basic", b.columns["doc"], b.columns["pos"], None),
                ("expanded", e.columns["doc"], e.columns["pos"], e.columns["dist"]),
                ("stop", s.columns["doc"], s.columns["pos"], None),
                ("first", f.columns["doc"], f.columns["pos"], None),
                ("ordinary", o.columns["doc"], o.columns["pos"], None),
                ("multi", m["doc"], m["pos"], m["dist"])):
            self.bases[name] = off
            n_pad = packed[name].n_padded
            assert n_pad >= len(doc)
            off += n_pad
            docs.append(pad_block_multiple(np.asarray(doc, np.int32), n_pad))
            poss.append(pad_block_multiple(np.asarray(pos, np.int32), n_pad))
            dists.append(pad_block_multiple(
                np.asarray(dist, np.int8) if dist is not None
                else np.zeros(len(doc), np.int8), n_pad))
            real = np.zeros(n_pad, bool)
            real[:len(doc)] = True
            reals.append(real)
        self.arena_doc_np = np.concatenate(docs)
        self.arena_pos_np = np.concatenate(poss)
        self.arena_dist_np = np.concatenate(dists)
        # pads (stream tails; incl. the multi stream's internal pair pad)
        # must never enter a serve dp-shard selection
        self.arena_real_np = np.concatenate(reals)
        self.arena_real_np[self.bases["multi"]:
                           self.bases["multi"]
                           + index.multi_key.pair_pad][
            index.multi_key.pairs.n_postings:] = False
        self.packed = concat_packed([packed[n] for n in self.bases])
        self.near_stop_np = np.asarray(index.basic.near_stop, np.int16)
        # device copies are lazy: the serve tier builds per-dp-shard arenas
        # from the numpy columns and must not also hold a full global copy
        # on device
        self._dev_arena = None
        self.max_distance = int(index.basic.max_distance)
        self.n_docs = int(max((int(d.max()) + 1 for d in docs if len(d)),
                              default=0))
        self.max_pos = int(max((int(p.max()) for p in poss if len(p)),
                               default=0))
        # widest |dist| any pivot_from_dist fetch can add to a position
        # (expanded reach / multi-key NeighborDistance) — part of the
        # 17-bit packed-key safety budget
        self.max_shift = int(np.abs(self.arena_dist_np).max(initial=0))
        if docs_per_shard is None:
            # auto-pick the segmentation grain from posting-list stats:
            # smaller per-row sort slabs beat one big slab (ROADMAP
            # shard_scaling) — results are identical at any grain
            from repro.core.builder import auto_docs_per_shard
            docs_per_shard = auto_docs_per_shard(self.n_docs,
                                                 index.max_posting_run())
        self.docs_per_shard = max(1, min(docs_per_shard, DOCS_PER_SHARD))
        # global shard grid: shard ids count from GLOBAL doc 0 so every
        # segment of a growing corpus buckets on the same boundaries
        self.doc_base = int(doc_base)
        self.n_shards = max(1, -(-(self.doc_base + self.n_docs)
                                 // self.docs_per_shard))

    @property
    def device_arena(self) -> dict:
        """The packed block arena + stream-3 slots as device arrays — the
        only index bytes the jit'd step ever touches."""
        if self._dev_arena is None:
            p = self.packed
            self._dev_arena = {
                "lanes": jnp.asarray(p.lanes),
                "blk_meta": jnp.asarray(p.meta_matrix()),
                "near_stop": jnp.asarray(self.near_stop_np),
            }
        return self._dev_arena

    def device_nbytes(self) -> int:
        """Bytes the device arena holds (packed lanes + block metadata +
        stream-3 slots)."""
        return self.packed.nbytes() + self.near_stop_np.nbytes


@dataclasses.dataclass
class _Task:
    """One subplan (or its doc-only fallback): the host-side merge unit."""
    plan_i: int            # which plan in the batch
    subplan_i: int
    fallback: bool         # doc-only fallback task (stream-1)
    stop_checks: tuple     # seed group's near-stop checks
    mode: str = MODE_PHRASE
    ranked: bool = False   # proximity scoring rides the bucket step
    score_bias: float = 0.0   # n_slots - n_groups (see SubPlan.n_slots)
    rows: list = dataclasses.field(default_factory=list)

    def collect_keys(self) -> np.ndarray:
        parts = [r.keys for r in self.rows if r.keys is not None and len(r.keys)]
        return np.concatenate(parts) if parts else np.empty(0, np.int64)

    def collect_scores(self) -> np.ndarray:
        parts = [r.scores for r in self.rows
                 if r.scores is not None and len(r.scores)]
        return np.concatenate(parts) if parts else np.empty(0, np.float32)


@dataclasses.dataclass
class _RowGroup:
    band: int
    slots: list            # [(ResolvedFetch, arena_start, length)] — absolute


@dataclasses.dataclass
class _Row:
    """One (task × doc shard) execution row of the fetch tables."""
    task: _Task
    shard: int             # doc-shard id (0 when unsharded / doc-only)
    shard_base: int        # first doc of the shard (re-basing origin)
    groups: list           # seed-first ordered _RowGroups, shard-clipped
    sortfree: bool = False  # constraint keys already ascending (see below)
    # filled after execution:
    keys: np.ndarray | None = None
    scores: np.ndarray | None = None   # ranked rows only, aligned with keys


def bucket_step_math(arena, t, *,
                     P0: int, P: int, impl: str, interpret: bool,
                     presorted: bool = False, ranked: bool = False,
                     kword: bool = False):
    """One shape bucket of segmented rows: gather packed lanes → vectorized
    unpack (ops.unpack_postings over the bit-packed block arena) → keys →
    per-row int32 rebase against `shard_base` → banded rows intersection.
    The seed (group 0) gets its own pad P0 — the planner seeds with the
    RAREST list, so the membership probe side stays narrow while constraint
    groups pad to P.  Rows are shard-clipped host-side, so there is no
    per-shard device loop and no in-shard masking.  `arena` is the packed
    device dict (BatchDeviceIndex.device_arena: lanes + per-block metadata +
    the raw stream-3 `near_stop` slots).  Returns (seed global keys
    [T, F*P0] int64, found [T, F*P0] bool) — plus proximity scores
    [T, F*P0] float32 when `ranked` (see api.py: bias + w(seed delta) + sum
    over constraint groups of w(banded min key-distance + stored |dist|
    delta), computed in this one fused pass from the postings already
    gathered).  Pure trace function — the engine jit-wraps it
    (`_batch_step`) and the serve tier calls it inside shard_map."""
    T, G, F = t["start"].shape
    near_stop = arena["near_stop"]
    A = arena["blk_meta"].shape[0] * BLOCK
    dt1 = t["doc_task"]
    base = t["shard_base"].astype(jnp.int64)

    def gather(sl, Pw):
        """Keys for group slice `sl` padded to Pw: [T, g, F, Pw] (+ the
        per-posting score delta when ranked)."""
        start, length = t["start"][:, sl], t["length"][:, sl]
        offset, req = t["offset"][:, sl], t["req_dist"][:, sl]
        maxab, pfd = t["max_abs"][:, sl], t["pivot_from_dist"][:, sl]
        iota = jnp.arange(Pw, dtype=jnp.int32)
        idx = jnp.clip(start[..., None] + iota, 0, A - 1)
        valid = iota < length[..., None]
        with jax.named_scope("unpack"):
            doc, pos, dist = unpack_postings(arena, idx, implementation=impl,
                                             interpret=interpret)
        valid &= (req[..., None] == NO_DIST) | (dist == req[..., None])
        valid &= jnp.abs(dist) <= maxab[..., None]
        valid &= t["active"][:, sl, None, None]
        # global 63-bit keys (identical to the flexible executor's packing)
        pos_eff = pos + jnp.where(pfd[..., None], dist, 0)
        low = pos_eff.astype(jnp.int64) - offset[..., None] + PHRASE_BIAS
        doc64 = doc.astype(jnp.int64)
        gk = jnp.where(dt1[:, None, None, None], doc64,
                       (doc64 << POS_BITS) | low)
        if not ranked:
            return idx, jnp.where(valid, gk, SENTINEL), None
        sfd = t["score_from_dist"][:, sl]
        delta = jnp.where(sfd[..., None], jnp.abs(dist), 0)
        return idx, jnp.where(valid, gk, SENTINEL), delta

    m26 = (1 << POS_BITS) - 1

    def rebase(gk, dt_b, b):
        """Row-local int32 re-basing (doc-only keys ARE doc ids: globally
        comparable in int32, no re-basing needed)."""
        dglob = jnp.where(dt_b, gk, gk >> POS_BITS)
        k32 = jnp.where(dt_b, gk, ((dglob - b) << TABLE_POS_BITS) | (gk & m26))
        return jnp.where(gk < SENTINEL, k32, I32_SENTINEL).astype(jnp.int32)

    with jax.named_scope("gather"):
        idx0, gk0, delta0 = gather(slice(0, 1), P0)
        gk0 = gk0[:, 0]                                        # [T, F, P0]

        # near-stop verification on the seed group (type-4 pivot checks)
        C = t["ns_packed"].shape[1]
        if C > 0:
            nb = near_stop.shape[0]
            ns = near_stop[jnp.clip(idx0[:, 0], 0, nb - 1)]    # [T, F, P0, K]
            ok = jnp.ones((T, F, P0), bool)
            Mns = t["ns_packed"].shape[2]
            for c in range(C):
                hit_c = jnp.zeros((T, F, P0), bool)
                for m in range(Mns):
                    tgt = t["ns_packed"][:, c, m][:, None, None, None]
                    val = t["ns_valid"][:, c, m][:, None, None]
                    hit_c |= (ns == tgt).any(axis=-1) & val
                has_check = t["ns_valid"][:, c].any(axis=-1)[:, None, None]
                ok &= hit_c | ~has_check
            gk0 = jnp.where(ok, gk0, SENTINEL)
        a64 = gk0.reshape(T, F * P0)
        a32 = rebase(gk0, dt1[:, None, None],
                     base[:, None, None]).reshape(T, F * P0)

    @jax.named_scope("intersect")
    def kword_found(b32_sorted):
        """K-way windowed span join (kword buckets): per-group signed delta
        masks, window-start scans ANDed across groups (core/kword.py;
        ops.banded_delta_mask_rows + kword_window_hits).  Every active
        constraint group of a kword task is banded at the task's window W
        (plan construction), so the per-row W is the max over group bands
        (inactive pads are band 0 and never constrain)."""
        a_rows = jnp.broadcast_to(a32[:, None], (T, G - 1, F * P0))
        masks = banded_delta_mask_rows(
            a_rows.reshape(T * (G - 1), F * P0),
            b32_sorted.reshape(T * (G - 1), F * P),
            jnp.broadcast_to(t["band"][:, 1:], (T, G - 1)).reshape(-1),
            implementation=impl, interpret=interpret)
        masks = masks.reshape(T, G - 1, F * P0).transpose(1, 0, 2)
        kw_bands = t["band"][:, 1:].max(axis=1)
        active = t["active"][:, 1:].transpose(1, 0)
        return kword_window_hits(masks, active, kw_bands)

    @jax.named_scope("gather")
    def gather_constraints():
        """Constraint groups' int32 row keys [T, G-1, F*P] (+ their score
        deltas when ranked)."""
        _, gkc, deltac = gather(slice(1, None), P)             # [T, G-1, F, P]
        b32 = rebase(gkc, dt1[:, None, None, None],
                     base[:, None, None, None]).reshape(T, G - 1, F * P)
        return b32, deltac

    if ranked:
        # proximity scores, canonical accumulation order (mirrored exactly by
        # Executor._run_groups_ranked): per-task bias, the seed's own delta,
        # then each constraint group seed-first.  Constraint deltas come from
        # one banded min-(key distance + |dist|) pass per group — the scoring
        # twin of the boolean membership test, on the same gathered slab.
        score = t["score_bias"][:, None] + proximity_w(delta0[:, 0].reshape(T, F * P0))
        found = jnp.ones((T, F * P0), bool)
        if G > 1:
            b32, deltac = gather_constraints()
            dl = deltac.reshape(T, G - 1, F * P)
            bands = t["band"][:, 1:]                           # [T, G-1]
            if impl == "pallas":
                with jax.named_scope("sort"):
                    b_sorted = jnp.sort(
                        jnp.where(b32 == I32_SENTINEL, jnp.int64(1) << 40,
                                  (b32.astype(jnp.int64) << SCORE_DELTA_BITS)
                                  | dl.astype(jnp.int64)), axis=-1)
                    bk = (b_sorted >> SCORE_DELTA_BITS).astype(jnp.int32)
                    bk = jnp.where(b_sorted >= jnp.int64(1) << 40,
                                   I32_SENTINEL, bk)
                    bd = (b_sorted
                          & ((1 << SCORE_DELTA_BITS) - 1)).astype(jnp.int32)
                with jax.named_scope("intersect"):
                    a_rows = jnp.broadcast_to(a32[:, None],
                                              (T, G - 1, F * P0))
                    delta_g = banded_min_delta_rows(
                        a_rows.reshape(T * (G - 1), F * P0),
                        bk.reshape(T * (G - 1), F * P),
                        bd.reshape(T * (G - 1), F * P),
                        jnp.broadcast_to(bands, (T, G - 1)).reshape(-1),
                        implementation=impl, interpret=interpret)
                    delta_g = delta_g.reshape(T, G - 1, F * P0)
            else:
                pad = jnp.int64(1) << 40
                with jax.named_scope("sort"):
                    comp = jnp.where(
                        b32 == I32_SENTINEL, pad,
                        (b32.astype(jnp.int64) << SCORE_DELTA_BITS)
                        | dl.astype(jnp.int64))
                    comp = jnp.sort(comp, axis=-1)
                with jax.named_scope("intersect"):
                    probe = jnp.where(
                        a32 == I32_SENTINEL, pad,
                        a32.astype(jnp.int64) << SCORE_DELTA_BITS)
                    probe = jnp.broadcast_to(probe[:, None],
                                             (T, G - 1, F * P0))
                    delta_g = scored_probe(
                        comp.reshape(T * (G - 1), F * P),
                        probe.reshape(T * (G - 1), F * P0),
                        jnp.broadcast_to(bands, (T, G - 1)).reshape(-1, 1))
                    delta_g = delta_g.reshape(T, G - 1, F * P0)
            active_c = t["active"][:, 1:, None]
            for gi in range(G - 1):
                hit_g = delta_g[:, gi] < I32_SENTINEL
                live = hit_g & active_c[:, gi]
                score = score + jnp.where(live, proximity_w(delta_g[:, gi]), 0.0)
                found &= hit_g | ~active_c[:, gi]
            if kword:
                # kword found = the span join, not pairwise membership; a
                # span match implies an in-band hit for every group, so the
                # score accumulated above is exact for every survivor (and
                # zeroed below for the rest)
                with jax.named_scope("sort"):
                    b32 = jnp.sort(b32, axis=-1)
                found = kword_found(b32)
        found &= a32 != I32_SENTINEL
        return a64, found, jnp.where(found, score, 0.0)
    if G > 1:
        b32, _ = gather_constraints()
        if not presorted:
            with jax.named_scope("sort"):
                b32 = jnp.sort(b32, axis=-1)
        if kword:
            found = kword_found(b32)
            return a64, found & (a32 != I32_SENTINEL)
        with jax.named_scope("intersect"):
            a_rows = jnp.broadcast_to(a32[:, None], (T, G - 1, F * P0))
            hit = banded_intersect_rows(
                a_rows.reshape(T * (G - 1), F * P0),
                b32.reshape(T * (G - 1), F * P),
                jnp.broadcast_to(t["band"][:, 1:], (T, G - 1)).reshape(-1),
                implementation=impl, interpret=interpret)
            hit = hit.reshape(T, G - 1, F * P0) | ~t["active"][:, 1:, None]
            found = hit.all(axis=1)
    else:
        found = jnp.ones((T, F * P0), bool)
    return a64, found & (a32 != I32_SENTINEL)


_batch_step = partial(jax.jit, static_argnames=(
    "P0", "P", "impl", "interpret", "presorted", "ranked",
    "kword"))(bucket_step_math)


class BatchExecutor:
    """Executes a batch of QueryPlans with result parity vs. the flexible
    `Executor` (same doc/pos sets, same postings accounting, same fallback
    semantics), but in O(#shape-buckets) jit dispatches instead of
    O(#queries * #groups) — and O(arena) gather/sort work total regardless
    of the doc-shard count (segmented rows)."""

    def __init__(self, index: IndexSet, flex: Executor | None = None,
                 impl: str | None = None, interpret: bool | None = None,
                 docs_per_shard: int | None = None, doc_base: int = 0):
        self.index = index
        self.dev = BatchDeviceIndex(index, docs_per_shard=docs_per_shard,
                                    doc_base=doc_base)
        self.flex = flex or Executor(index)
        # None = the platform's choice (Pallas, compiled, on a TPU; the jnp
        # reference elsewhere); "ref" stays selectable explicitly
        self.impl, self.interpret = resolve_kernels(impl, interpret)
        # packed-key safety: positions (plus bias, the widest dist shift,
        # and the widest band) must fit the 17-bit in-doc field or
        # cross-doc false positives appear
        self._pos_budget = (1 << TABLE_POS_BITS) - PHRASE_BIAS \
            - self.dev.max_pos - max(self.dev.max_distance,
                                     self.dev.max_shift)
        # bucket-step calls: padded slab against live rows and gathered
        # postings (elements), banded rows and those the kernels pack, and
        # calls of a step key not run before;
        # shard dispatchers may call one executor from several threads
        self.slab_stats = {"steps": 0, "slab_rows": 0, "live_rows": 0,
                           "slab_elems": 0, "live_elems": 0, "first_runs": 0,
                           "banded_rows": 0, "packed_rows": 0}
        self._ran: set = set()
        self._stats_lock = threading.Lock()

    # -- tensorization ------------------------------------------------------

    def _caps(self):
        """(g_cap, f_cap, split_cap, p0_cap, p_cap) — module globals by
        default so tests can shrink them; the serve executor overrides with
        its fixed-shape table limits (p0_cap = seed pad, p_cap = constraint
        pad)."""
        return G_CAP, F_CAP, F_SPLIT_CAP, P_CAP, P_CAP

    def _order_groups(self, groups, ranked=False):
        """Seed-first ordering; None when no valid seed exists.  Shared with
        the flexible ranked path (executor.order_groups_seed_first) so the
        two executors accumulate float32 scores in the same group order
        (ranked ordering is plan-order deterministic — see
        order_groups_seed_first)."""
        return order_groups_seed_first(groups, ranked=ranked)

    def _task_fits(self, groups, kword: bool = False) -> bool:
        g_cap, f_cap, _, _, _ = self._caps()
        if len(groups) > g_cap:
            return False
        for g in groups:
            if len(g.fetches) > f_cap:
                return False
            if int(g.band) > self._pos_budget:
                return False
            # kword delta masks are int32 bitfields over d in [-W, W]: wider
            # windows ride the flexible escape path (int64 masks, W <= 31)
            if kword and int(g.band) > KW_DEVICE_MAX_WINDOW:
                return False
            for f in g.fetches:
                if f.stream == "first" and not _is_first_group(g):
                    return False
        return True

    def _build_rows(self, task: _Task, ordered) -> list | None:
        """Segment a task at doc-shard boundaries: one row per shard the
        SEED group touches, every fetch clipped to the shard's sub-slice
        (the arena is doc-sorted per fetch, so a shard's rows are one
        `searchsorted` away).  Fetches longer than p_cap split across extra
        F slots of the same group (slot unions).  None => plan goes flex."""
        d = self.dev
        dps = d.docs_per_shard
        base = d.doc_base
        _, _, split_cap, p0_cap, p_cap = self._caps()
        p0_cap, p_cap = max(1, p0_cap), max(1, p_cap)
        # arena doc ids are LOCAL; shard ids live on the GLOBAL grid
        sh_lo = base // dps
        sh_hi = (base + max(d.n_docs - 1, 0)) // dps
        if sh_lo == sh_hi:
            per_group = [{sh_lo: [(f, d.bases[f.stream] + f.start, f.length)
                                  for f in g.fetches]} for g in ordered]
            seed_shards = [sh_lo]
        else:
            per_group = []
            for g in ordered:
                m: dict = {}
                for f in g.fetches:
                    s0 = d.bases[f.stream] + f.start
                    arr = d.arena_doc_np[s0:s0 + f.length]
                    lo = (int(arr[0]) + base) // dps
                    hi = (int(arr[-1]) + base) // dps
                    if lo == hi:
                        m.setdefault(lo, []).append((f, s0, f.length))
                        continue
                    cuts = np.searchsorted(
                        arr, np.arange(lo + 1, hi + 1) * dps - base)
                    edges = np.concatenate(([0], cuts, [f.length]))
                    for i in range(len(edges) - 1):
                        ln = int(edges[i + 1] - edges[i])
                        if ln:
                            m.setdefault(lo + i, []).append(
                                (f, s0 + int(edges[i]), ln))
                per_group.append(m)
            seed_shards = sorted(per_group[0])
        rows = []
        for sh in seed_shards:
            shard_base = sh * dps - base       # local re-basing origin
            groups, sortfree = [], True
            for gi in range(len(ordered)):
                cap = p0_cap if gi == 0 else p_cap
                slots = []
                for f, s, ln in per_group[gi].get(sh, ()):
                    while ln > cap:
                        slots.append((f, s, cap))
                        s += cap
                        ln -= cap
                    slots.append((f, s, ln))
                if len(slots) > split_cap:
                    return None
                if gi > 0:
                    # sort-free: a single unsplit slot gathers ascending keys
                    # (the arena is (doc, pos)-sorted per fetch and the key
                    # packings are monotone); dist/pivot masks punch holes
                    # mid-row and multi-slot unions interleave — both break
                    # order.  Trailing pads sort last, so they are harmless.
                    if len(slots) > 1:
                        sortfree = False
                    for f, _, _ in slots:
                        if (f.required_dist is not None
                                or f.max_abs_dist is not None
                                or f.pivot_from_dist):
                            sortfree = False
                groups.append(_RowGroup(band=int(ordered[gi].band), slots=slots))
            rows.append(_Row(task=task, shard=sh, shard_base=shard_base,
                             groups=groups, sortfree=sortfree))
        return rows

    def _build_tasks(self, plan_i: int, plan: QueryPlan, tasks: list,
                     ranked: bool = False) -> bool:
        """Append tasks (with segmented rows) for one plan; False => route
        plan to the flexible executor (table caps exceeded)."""
        if self._pos_budget <= 0:
            return False
        out = []
        for sp_i, sp in enumerate(plan.subplans):
            if not sp.supported:
                continue
            main_dead = (not sp.groups) or any(not g.fetches for g in sp.groups)
            if not main_dead:
                ordered = self._order_groups(sp.groups, ranked=ranked)
                if ordered is None or not self._task_fits(
                        ordered, kword=sp.mode == MODE_KWORD):
                    return False
                checks = ordered[0].fetches[0].stop_checks
                if any(f.stop_checks != checks for f in ordered[0].fetches) or \
                   any(f.stop_checks for g in ordered[1:] for f in g.fetches):
                    return False
                task = _Task(plan_i, sp_i, False, checks, mode=sp.mode,
                             ranked=ranked,
                             score_bias=float(sp.n_slots - len(sp.groups)))
                task.rows = self._build_rows(task, ordered)
                if task.rows is None:
                    return False
                out.append(task)
            if sp.fallback_groups:
                fb_dead = any(not g.fetches for g in sp.fallback_groups)
                if not fb_dead:
                    ordered = self._order_groups(sp.fallback_groups)
                    if ordered is None or not self._task_fits(ordered):
                        return False
                    # fallback tasks are validated eagerly (the flex-routing
                    # decision must not depend on results) but executed
                    # lazily: only when the main task comes back empty
                    task = _Task(plan_i, sp_i, True, (), mode=MODE_PHRASE)
                    task.rows = self._build_rows(task, ordered)
                    if task.rows is None:
                        return False
                    out.append(task)
        tasks.extend(out)
        return True

    def _bucket_key(self, row: _Row):
        G = max(2, _next_pow2(len(row.groups), floor=2))
        F = _next_pow2(max(len(g.slots) for g in row.groups), floor=1)
        P0 = _next_pow2(max((ln for _, _, ln in row.groups[0].slots),
                            default=1), floor=P_FLOOR)
        P = _next_pow2(max((ln for g in row.groups[1:] for _, _, ln in g.slots),
                           default=1), floor=P_FLOOR)
        # near-stop slots are padded to coarse buckets (invalid slots are
        # inert) so check-count variation doesn't multiply compile shapes
        checks = row.task.stop_checks
        if checks:
            C = _next_pow2(len(checks), floor=4)
            M = _next_pow2(max(len(ids) for _, ids in checks), floor=2)
        else:
            C = M = 0
        # only big slabs are worth a separate sort-free compile shape; for
        # small P the sort is cheap and splitting buckets costs more calls
        # (ranked rows always sort: scoring needs the composite order)
        sortfree = row.sortfree and P >= 2048 and not row.task.ranked
        return (G, F, P0, P, C, M, sortfree, row.task.ranked,
                row.task.mode == MODE_KWORD)

    def _tensorize_bucket(self, rows: list, G: int, F: int, C: int, M: int,
                          T_pad: int) -> dict:
        t = alloc_batch_tables(T_pad, G, F, C, M)
        for ti, row in enumerate(rows):
            task = row.task
            t["doc_task"][ti] = task.fallback
            t["shard_base"][ti] = row.shard_base
            t["score_bias"][ti] = task.score_bias
            if task.stop_checks:
                pack_ns_checks(t, ti, task.stop_checks, self.dev.max_distance)
            for gi, g in enumerate(row.groups):
                t["band"][ti, gi] = g.band
                t["active"][ti, gi] = True
                for fi, (f, s, ln) in enumerate(g.slots):
                    t["start"][ti, gi, fi] = s
                    t["length"][ti, gi, fi] = ln
                    # mirror Executor._fetch_keys key selection
                    if f.stream == "first":
                        continue                        # doc key: no offset
                    phrase_keyed = (
                        f.stream == "stop"
                        or (f.stream == "expanded" and f.required_dist is not None)
                        or (f.stream in ("basic", "ordinary")
                            and task.mode == MODE_PHRASE))
                    if phrase_keyed:
                        t["offset"][ti, gi, fi] = f.offset
                    if f.required_dist is not None:
                        t["req_dist"][ti, gi, fi] = f.required_dist
                    if f.max_abs_dist is not None:
                        t["max_abs"][ti, gi, fi] = f.max_abs_dist
                    t["pivot_from_dist"][ti, gi, fi] = bool(f.pivot_from_dist)
                    t["score_from_dist"][ti, gi, fi] = \
                        bool(f.score_delta_from_dist)
        return t

    # -- execution ----------------------------------------------------------

    @staticmethod
    def _scatter_row_keys(part: list, a64: np.ndarray, found: np.ndarray,
                          scores: np.ndarray | None = None):
        """Assign each row its found seed keys (and scores, when ranked) —
        one pass over the hit mask instead of T boolean-indexings.  Shared
        with the serve executor so the result-extraction semantics can never
        diverge."""
        hit_rows, cols = np.nonzero(found)
        keys = a64[hit_rows, cols]
        splits = np.searchsorted(hit_rows, np.arange(1, len(part)))
        for ti, row_keys in enumerate(np.split(keys, splits)):
            part[ti].keys = row_keys
        if scores is not None:
            svals = scores[hit_rows, cols].astype(np.float32)
            for ti, row_scores in enumerate(np.split(svals, splits)):
                part[ti].scores = row_scores

    def _bucket_chunks(self, rows: list):
        """Yield (rows, device tables, static step kwargs), one per jit'd
        bucket-step call over `rows`."""
        buckets: dict = {}
        with obs.span("batch.rows"):
            for row in rows:
                buckets.setdefault(self._bucket_key(row), []).append(row)
        d = self.dev
        for (G, F, P0, P, C, M, sortfree, ranked, kword), rs in buckets.items():
            per_task = F * P0 + (G - 1) * F * P
            if C > 0:                  # near-stop gather adds an [F, P0, K] slab
                per_task += F * P0 * int(d.near_stop_np.shape[1])
            chunk = max(1, GATHER_BUDGET // per_task)
            for lo in range(0, len(rs), chunk):
                part = rs[lo:lo + chunk]
                # tight T padding: big-P buckets usually hold 1-4 rows, and
                # padding them to a large T multiplies the gather/sort slab;
                # the extra pow2 compile variants are absorbed by warm-up
                T_pad = _next_pow2(len(part), floor=4)
                with obs.span("batch.tensorize"):
                    t = self._tensorize_bucket(part, G, F, C, M, T_pad)
                # the score columns are only read by the ranked program —
                # keep them off the per-call transfer path for unranked
                # buckets (device_put per table entry is the step's fixed
                # cost at smoke scale)
                with obs.span("batch.transfer"):
                    tj = {k: jnp.asarray(v) for k, v in t.items()
                          if ranked or k not in ("score_bias",
                                                 "score_from_dist")}
                yield part, tj, dict(P0=P0, P=P, impl=self.impl,
                                     interpret=self.interpret,
                                     presorted=sortfree, ranked=ranked,
                                     kword=kword)

    def _count_slab(self, part: list, T: int, shape: tuple):
        """Count one bucket-step call of `part` padded to T rows of bucket
        `shape` (G, F, P0, P): each row gathers F*P0 + (G-1)*F*P postings
        and runs G-1 banded rows of F*P0 seed keys against F*P constraint
        keys, packed where `packed_layout` says the kernels pack them."""
        G, F, P0, P = shape
        live = sum(ln for row in part for g in row.groups
                   for _, _, ln in g.slots)
        banded = T * (G - 1)
        with self._stats_lock:
            st = self.slab_stats
            st["steps"] += 1
            st["slab_rows"] += T
            st["live_rows"] += len(part)
            st["slab_elems"] += T * (F * P0 + (G - 1) * F * P)
            st["live_elems"] += live
            st["banded_rows"] += banded
            st["packed_rows"] += banded if packed_layout(F * P0, F * P) else 0

    @staticmethod
    def _step_key(tj: dict, static: dict) -> tuple:
        """A bucket-step call's identity: its static arguments and table
        shapes (what selects a compiled program)."""
        return (tuple(sorted(static.items())),
                tuple(sorted((k, v.shape) for k, v in tj.items())))

    def _first_run(self, key: tuple):
        """A `batch.first_run` span (and a count) around a step call of a
        key this executor has not run before; nothing otherwise."""
        with self._stats_lock:
            if key in self._ran:
                return contextlib.nullcontext()
            self._ran.add(key)
            self.slab_stats["first_runs"] += 1
        return obs.span("batch.first_run")

    def _run_rows(self, rows: list):
        for part, tj, static in self._bucket_chunks(rows):
            T, G, F = tj["start"].shape
            self._count_slab(part, T, (G, F, static["P0"], static["P"]))
            with self._first_run(self._step_key(tj, static)):
                with obs.span("batch.step"):
                    out = _batch_step(self.dev.device_arena, tj, **static)
                with obs.span("batch.fetch"):
                    out = [np.asarray(x) for x in out]
            with obs.span("batch.scatter"):
                self._scatter_row_keys(part, *out)

    def lower_steps(self, plans: list[QueryPlan],
                    requests: list[SearchRequest]) -> dict:
        """The bucket-step calls that the main round of
        `execute_batch(plans, requests=requests)` makes, lowered
        (`jax.stages.Lowered`) and keyed by their static arguments and table
        shapes.  Compiling one fills the same cache the call would, so a
        cold start can compile them concurrently; the compiled text shows
        which kernels a served step runs."""
        tasks: list[_Task] = []
        for i, (plan, req) in enumerate(zip(plans, requests)):
            self._build_tasks(i, plan, tasks, ranked=req.rank)
        rows = [r for t in tasks if not t.fallback for r in t.rows]
        out = {}
        for _, tj, static in self._bucket_chunks(rows):
            key = self._step_key(tj, static)
            if key not in out:
                out[key] = _batch_step.lower(self.dev.device_arena, tj,
                                             **static)
        return out

    # -- merge (mirrors Executor.execute) -----------------------------------

    def _merge_plan(self, plan: QueryPlan, task_map: dict,
                    request: SearchRequest | None) -> SearchResult:
        ranked = request is not None and request.rank
        all_keys, all_scores, doc_only_keys = [], [], []
        postings = 0
        used_fallback = False
        types = []
        for sp_i, sp in enumerate(plan.subplans):
            if not sp.supported:
                continue
            types.append(sp.qtype)
            postings += sp.postings_read
            main = task_map.get((sp_i, False))
            keys = main.collect_keys() if main is not None else np.empty(0, np.int64)
            scores = (main.collect_scores() if ranked and main is not None
                      else np.empty(0, np.float32))
            if len(keys) == 0 and sp.fallback_groups:
                used_fallback = True
                postings += sum(g.postings_read for g in sp.fallback_groups)
                fb = task_map.get((sp_i, True))
                dkeys = fb.collect_keys() if fb is not None else np.empty(0, np.int64)
                doc_only_keys.append(dkeys)
                keys, scores = keys[:0], scores[:0]
            all_keys.append(keys)
            all_scores.append(scores)
        return merge_subplan_results(all_keys, doc_only_keys, postings,
                                     used_fallback, tuple(types), request,
                                     all_scores=all_scores)

    # -- public API ---------------------------------------------------------

    def execute_batch(self, plans: list[QueryPlan],
                      max_results: int | None = None,
                      requests: list[SearchRequest] | None = None
                      ) -> list[SearchResult]:
        """Requests (when given) align 1:1 with plans and carry ranking /
        top_k; plans stay the executor's input so escape routing and table
        building see resolved fetches only."""
        if requests is None:
            requests = [SearchRequest((), top_k=max_results)] * len(plans)
        tasks: list[_Task] = []
        flex_plans: dict[int, QueryPlan] = {}
        plan_tasks: dict[int, list] = {}
        with obs.span("batch.rows"):
            for i, plan in enumerate(plans):
                start = len(tasks)
                if self._build_tasks(i, plan, tasks, ranked=requests[i].rank):
                    plan_tasks[i] = tasks[start:]
                else:
                    flex_plans[i] = plan
            main_rows = [r for t in tasks if not t.fallback for r in t.rows]
        # round 1: main rows; round 2: only the fallback rows whose main
        # result came back empty (mirrors the flexible executor, which never
        # touches stream 1 when the positional search hits)
        self._run_rows(main_rows)
        with obs.span("batch.rows"):
            main_keys = {(t.plan_i, t.subplan_i): t.collect_keys()
                         for t in tasks if not t.fallback}
            fallback_rows = [r for t in tasks if t.fallback
                             and len(main_keys.get((t.plan_i, t.subplan_i),
                                                   np.empty(0))) == 0
                             for r in t.rows]
        self._run_rows(fallback_rows)
        out: list[SearchResult | None] = [None] * len(plans)
        with obs.span("batch.merge"):
            for i, plan in enumerate(plans):
                if i in flex_plans:
                    out[i] = self.flex.execute(plan, request=requests[i])
                else:
                    task_map = {(t.subplan_i, t.fallback): t
                                for t in plan_tasks[i]}
                    out[i] = self._merge_plan(plan, task_map, requests[i])
        return out


def _is_first_group(g) -> bool:
    return all(f.stream == "first" for f in g.fetches)
