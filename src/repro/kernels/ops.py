"""jit'd public wrappers around the Pallas kernels.

Each op takes `implementation='pallas' | 'ref'` and, for the pallas path,
`interpret=`.  Which of them a caller gets by default is decided here, from
the platform, and nowhere else (`resolve_kernels`): on a
TPU the served path runs the compiled Pallas kernels; elsewhere it runs the
jnp reference, and Pallas calls run in interpret mode (the tests' parity
oracle).  Tests sweep shapes/dtypes and assert the two implementations agree
exactly (integer ops) or to bf16 tolerance (attention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.intersect import (I32_SENTINEL, LANES, SUBLANES, TILE,
                                     banded_rows_pallas, packed_rows_pallas)
from repro.kernels.segment_bag import segment_bag_pallas
from repro.kernels.unpack import unpack_fields_pallas

_SDB = 4      # delta bits of the (key << 4 | delta) scoring composite
              # (== core.fetch_tables.SCORE_DELTA_BITS; kept literal here so
              # the kernel layer stays import-free of core)

# packed-postings block geometry (== core.postings.BLOCK/BLOCK_LOG2 and
# PACK_WIDTH_BITS; literal for the same core-import-free reason as _SDB)
_BLOCK_LOG2 = 7
_BLOCK = 1 << _BLOCK_LOG2
_WBITS = 6


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp(interpret: bool | None) -> bool:
    """Pallas interpret mode: None = off on a TPU, on everywhere else."""
    return not _on_tpu() if interpret is None else interpret


def resolve_kernels(impl: str | None,
                    interpret: bool | None) -> tuple[str, bool]:
    """(impl, interpret) with each None filled in from the platform: the
    compiled Pallas kernels on a TPU, the jnp 'ref' path elsewhere.  'ref'
    stays selectable by explicit argument."""
    return impl or ("pallas" if _on_tpu() else "ref"), _interp(interpret)


# ---------------------------------------------------------------------------
# packed-postings unpack
# ---------------------------------------------------------------------------

def unpack_fields(words: jax.Array, shifts: jax.Array, widths: jax.Array,
                  anchors: jax.Array, *, implementation: str = "pallas",
                  interpret: bool | None = None) -> jax.Array:
    """anchor + ((word >> shift) & mask(width)) elementwise — the bit-extract
    half of the packed-postings decode (any int32 shape; the Pallas path
    pads/reshapes to [R, 128] tiles)."""
    if implementation == "ref":
        mask = jnp.where(widths >= 32, jnp.int32(-1),
                         (jnp.int32(1) << jnp.minimum(widths, 31)) - 1)
        return anchors + ((words >> shifts) & mask)
    out = unpack_fields_pallas(words.reshape(-1), shifts.reshape(-1),
                               widths.reshape(-1), anchors.reshape(-1),
                               interpret=_interp(interpret))
    return out.reshape(words.shape)


def unpack_postings(arena: dict, idx: jax.Array, *,
                    implementation: str = "ref",
                    interpret: bool | None = None):
    """(doc, pos, dist) int32 for posting ordinals `idx` of a packed arena.

    arena: device dict with `lanes` [W] int32 packed delta words and
    `blk_meta` [NB, 5] int32 per-block metadata (column 0 = base lane word,
    1 = packed field widths, 2..4 = doc/pos/dist anchors — see
    core.postings.PackedPostings.meta_matrix; NB * 128 is the addressable
    ordinal range).  The metadata gathers and one lane gather per field are
    plain XLA gathers; the bit extract runs through `unpack_fields` (ref
    math or the Pallas kernel).  Out-of-range lane reads (width-0 tail
    blocks) rely on jnp's clamping gather semantics."""
    lanes = arena["lanes"]
    blk = idx >> _BLOCK_LOG2
    off = idx & (_BLOCK - 1)
    # one 1-D gather per metadata column: a row gather from the narrow
    # [NB, 5] matrix makes the chip's compiler plan temporaries of ~1 KB
    # per index at some NB (15 GB for a 15M-posting bucket)
    meta = [arena["blk_meta"][:, c][blk] for c in range(5)]
    base, bw = meta[0], meta[1]
    m = (1 << _WBITS) - 1
    ws = [bw & m, (bw >> _WBITS) & m, (bw >> (2 * _WBITS)) & m]
    fbs = [base, base + (ws[0] << 2), base + ((ws[0] + ws[1]) << 2)]
    words, shifts = [], []
    for w, fb in zip(ws, fbs):
        bit = off * w
        words.append(lanes[fb + (bit >> 5)])
        shifts.append(bit & 31)
    out = unpack_fields(jnp.stack(words), jnp.stack(shifts), jnp.stack(ws),
                        jnp.stack(meta[2:]),
                        implementation=implementation, interpret=interpret)
    return out[0], out[1], out[2]


# ---------------------------------------------------------------------------
# banded intersection
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row_block(p: int, req: int) -> tuple[int, int]:
    """(padded row width, block) for a row of width p: rows pad to whole
    (8, 128) tiles, and the block is the largest multiple of TILE that is at
    most max(req, TILE) and divides the padded width (blocks never straddle
    logical rows)."""
    p = _round_up(p, TILE)
    blk = max(req // TILE, 1) * TILE
    while p % blk:
        blk -= TILE
    return p, blk


def _pad_row(x: jax.Array, width: int, fill) -> jax.Array:
    pad = width - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill) if pad else x


def packed_layout(pa: int, pb: int) -> bool:
    """Whether banded rows of seed width `pa` against constraint width `pb`
    run in the packed layout (kernels/intersect.py): both round to at most
    one tile (8 lane planes).  The bucket step's slab counter reads the same
    predicate, so what it counts is what ran."""
    return max(_round_up(pa, LANES), _round_up(pb, LANES)) <= TILE


# vregs of a packed block's widest plane stack (rows = 8 * this / planes):
# on a TPU v5e 32 runs 6144 rows of 128 keys in half the time of 8, and
# rows of 512-1024 keys 3-4x faster; the spill to VMEM costs less than the
# grid steps it saves
_PACK_VREGS = 32


def _packed_rows(twin, a, b_planes, bands, interpret):
    """Packed host side: rows pad to a multiple of the block's row count
    with sentinel rows and to whole 128-lane planes, and each block of
    `rows` rows is laid out plane by plane, one row per sublane; the output
    comes back through the inverse transpose.  A block is skipped when no
    row of it holds a real a key and a real b key."""
    N, pa = a.shape
    sa = -(-pa // LANES)
    sb = -(-b_planes[0].shape[1] // LANES)
    rows = min(SUBLANES * max(_PACK_VREGS // max(sa, sb), 1),
               _round_up(N, SUBLANES))
    n_pad = _round_up(N, rows)

    def lay(x, planes, fill):          # [N, P] -> [blocks * planes * rows, 128]
        x = jnp.pad(x, ((0, n_pad - N), (0, planes * LANES - x.shape[1])),
                    constant_values=fill)
        return x.reshape(n_pad // rows, rows, planes, LANES) \
            .transpose(0, 2, 1, 3).reshape(-1, LANES)

    live = (a != I32_SENTINEL).any(axis=1) \
        & (b_planes[0] != I32_SENTINEL).any(axis=1)
    live = jnp.pad(live, (0, n_pad - N)).reshape(-1, rows).any(axis=1)
    band2d = jnp.broadcast_to(
        jnp.pad(bands.astype(jnp.int32), (0, n_pad - N))[:, None],
        (n_pad, LANES))
    out = packed_rows_pallas(
        twin, lay(a, sa, I32_SENTINEL),
        [lay(x, sb, I32_SENTINEL if i == 0 else 0)
         for i, x in enumerate(b_planes)],
        band2d, live.astype(jnp.int32), rows=rows,
        interpret=_interp(interpret))
    return out.reshape(n_pad // rows, sa, rows, LANES).transpose(0, 2, 1, 3) \
        .reshape(n_pad, sa * LANES)[:N, :pa]


def _banded_rows(twin, a, b_planes, bands, block_a, block_b, interpret):
    """Shared host side of the three banded row kernels (`twin` names one,
    kernels/intersect.py TWINS).  a: [N, Pa] int32; b_planes: aligned
    [N, Pb] int32 planes, the first one (the keys) sorted per row.

    Narrow rows (`packed_layout`) run packed, eight rows to a vreg
    (`_packed_rows`); wider ones tiled: rows pad to whole tiles (a and keys
    with I32_SENTINEL, other planes with 0), and per a-block the
    band-overlapping run of b blocks is found from the block minima and
    visited.  Sentinel a entries are left out of the block ranges: their
    output is never read."""
    N, pa = a.shape
    if packed_layout(pa, b_planes[0].shape[1]):
        return _packed_rows(twin, a, b_planes, bands, interpret)
    pa_pad, block_a = _row_block(pa, block_a)
    pb_pad, block_b = _row_block(b_planes[0].shape[1], block_b)
    a = _pad_row(a, pa_pad, I32_SENTINEL)
    b_planes = [_pad_row(x, pb_pad, I32_SENTINEL if i == 0 else 0)
                for i, x in enumerate(b_planes)]
    nab_pp = pa_pad // block_a            # a-blocks per row
    nbb_pp = pb_pad // block_b            # b-blocks per row

    # per-a-block value range over real keys (int64: +/- band must not wrap)
    a_t = a.reshape(N, nab_pp, block_a).astype(jnp.int64)
    real = a_t != I32_SENTINEL
    amin = jnp.where(real, a_t, I32_SENTINEL).min(axis=2)
    amax = jnp.where(real, a_t, -1).max(axis=2)
    b_block_min = b_planes[0].reshape(N, nbb_pp, block_b)[:, :, 0] \
        .astype(jnp.int64)
    band64 = bands.astype(jnp.int64)[:, None]
    # side='left' - 1: a block whose min equals amin-band may be preceded by
    # a block ending in the same value (duplicates straddling the boundary);
    # clip keeps the range inside the owning row
    lo = jax.vmap(lambda bm, q: jnp.searchsorted(bm, q, side="left"))(
        b_block_min, amin - band64)
    lo = jnp.clip(lo - 1, 0, nbb_pp - 1)
    hi = jax.vmap(lambda bm, q: jnp.searchsorted(bm, q, side="right"))(
        b_block_min, amax + band64)
    n_tiles = jnp.where(real.any(axis=2), jnp.maximum(hi - lo, 0), 0) \
        .astype(jnp.int32)
    # absolute b-block index: offset into the row's own b segment
    row_base = (jnp.arange(N, dtype=jnp.int64) * nbb_pp)[:, None]
    lo_abs = (lo + row_base).astype(jnp.int32)
    band_per_block = jnp.broadcast_to(bands.astype(jnp.int32)[:, None],
                                      (N, nab_pp))
    out2d = banded_rows_pallas(
        twin, a.reshape(-1, LANES), [x.reshape(-1, LANES) for x in b_planes],
        lo_abs.reshape(-1), n_tiles.reshape(-1), band_per_block.reshape(-1),
        block_a=block_a, block_b=block_b, max_tiles=nbb_pp,
        interpret=_interp(interpret))
    return out2d.reshape(N, pa_pad)[:, :pa]


def banded_intersect(a: jax.Array, b_sorted: jax.Array, band: int, *,
                     implementation: str = "pallas",
                     interpret: bool | None = None, block_a: int = TILE,
                     block_b: int = TILE) -> jax.Array:
    """found[i] = exists j with |a[i] - b_sorted[j]| <= band.

    a: [Na] int32 (any order); b_sorted: [Nb] int32 ascending.  Returns
    bool [Na].  Entries equal to I32_SENTINEL never match (padding).  The
    Pallas path is the one-row case of `banded_intersect_rows`.
    """
    assert a.dtype == jnp.int32 and b_sorted.dtype == jnp.int32
    if implementation == "ref":
        found = ref.banded_intersect_ref(a, b_sorted, band)
        return found & (a != I32_SENTINEL)
    if a.shape[0] == 0 or b_sorted.shape[0] == 0:
        return jnp.zeros(a.shape, jnp.bool_)
    return banded_intersect_rows(
        a[None], b_sorted[None], jnp.full((1,), band, jnp.int32),
        interpret=interpret, block_a=block_a, block_b=block_b)[0]


def banded_intersect_rows(a: jax.Array, b_sorted: jax.Array, bands: jax.Array,
                          *, implementation: str = "pallas",
                          interpret: bool | None = None, block_a: int = TILE,
                          block_b: int = TILE) -> jax.Array:
    """Batched banded membership: found[n, i] = exists j with
    |a[n, i] - b_sorted[n, j]| <= bands[n].

    a: [N, Pa] int32 (any order); b_sorted: [N, Pb] int32, ascending per row;
    bands: [N] int32 (DYNAMIC — one pallas program serves mixed band widths
    via scalar prefetch, so the batch executor never recompiles per band
    pattern).  I32_SENTINEL entries of `a` never match.  This is the engine
    hot path: each row is one (seed group, constraint group) membership test
    of a shard-segmented batch-executor row — the same call the serve tier
    runs inside shard_map, where every logical row's keys are re-based
    against its own doc shard.
    """
    assert a.dtype == jnp.int32 and b_sorted.dtype == jnp.int32
    N, pa = a.shape
    pb = b_sorted.shape[1]
    if implementation == "ref":
        def row(av, bv, band):
            lo = jnp.searchsorted(bv, av - band, side="left")
            hi = jnp.searchsorted(bv, av + band, side="right")
            return hi > lo
        found = jax.vmap(row)(a, b_sorted, bands.astype(jnp.int32))
        return found & (a != I32_SENTINEL)

    if N == 0 or pa == 0 or pb == 0:
        return jnp.zeros((N, pa), jnp.bool_)
    out = _banded_rows("intersect", a, [b_sorted], bands,
                       block_a, block_b, interpret)
    return (out > 0) & (a != I32_SENTINEL)


_KW_MAX_BAND = 15   # device kword window cap: bit (d + band) <= 30 per lane


def banded_delta_mask_rows(a: jax.Array, b_sorted: jax.Array,
                           bands: jax.Array, *,
                           implementation: str = "pallas",
                           interpret: bool | None = None, block_a: int = TILE,
                           block_b: int = TILE) -> jax.Array:
    """Batched signed-delta bitmask (the K-word join twin of
    `banded_intersect_rows`, core/kword.py): out[n, i] has bit
    (d + bands[n]) set iff exists j with b_sorted[n, j] - a[n, i] == d and
    |d| <= bands[n] — one int32 per anchor encoding WHICH offsets of the
    [-band, band] window hold a candidate for this constraint group.  The
    K-way combine then scans window starts t in [0, band]: a query matches
    at an anchor iff some t has every active group's mask non-zero in bits
    [t, t + band] (see `kword_window_hits` / bucket_step_math's kword pass).

    a: [N, Pa] int32 (any order); b_sorted: [N, Pb] int32 ascending per
    row; bands: [N] int32, each <= 15 (wider kword windows ride the flex
    escape — batch_executor._task_fits).  I32_SENTINEL entries of `a` map
    to mask 0.
    """
    assert a.dtype == jnp.int32 and b_sorted.dtype == jnp.int32
    N, pa = a.shape
    pb = b_sorted.shape[1]
    if implementation == "ref":
        def row(av, bv, band):
            mask = jnp.zeros_like(av)
            for d in range(-_KW_MAX_BAND, _KW_MAX_BAND + 1):
                lo = jnp.searchsorted(bv, av + d, side="left")
                hi = jnp.searchsorted(bv, av + d, side="right")
                present = (hi > lo) & (jnp.abs(d) <= band)
                mask = mask | jnp.where(
                    present, jnp.int32(1) << jnp.clip(d + band, 0, 31),
                    jnp.int32(0))
            return mask
        out = jax.vmap(row)(a, b_sorted, bands.astype(jnp.int32))
        return jnp.where(a == I32_SENTINEL, 0, out)

    if N == 0 or pa == 0 or pb == 0:
        return jnp.zeros((N, pa), jnp.int32)
    out = _banded_rows("delta_mask", a, [b_sorted], bands,
                       block_a, block_b, interpret)
    return jnp.where(a == I32_SENTINEL, 0, out)


def delta_mask_t_bits(mask: jax.Array, bands: jax.Array) -> jax.Array:
    """Per-group window scan of a delta mask: bit t of the result is set iff
    the group's mask has a candidate inside the window starting at offset
    t - W from the anchor, i.e. ((mask >> t) & low(W + 1)) != 0 for
    t in [0, W].  mask: [N, Pa] int32 from `banded_delta_mask_rows`;
    bands: [N] int32 (W <= 15).  The K-way combine is a plain AND of these
    per-group bit sets: the query matches at an anchor iff the AND over all
    active groups is non-zero (some shared window start survives)."""
    low = ((jnp.int32(1) << (bands + 1)) - 1)[:, None]     # (W+1) low bits
    bits = jnp.zeros_like(mask)
    for t in range(_KW_MAX_BAND + 1):
        hit = (((mask >> t) & low) != 0) & (t <= bands)[:, None]
        bits = bits | jnp.where(hit, jnp.int32(1) << t, jnp.int32(0))
    return bits


def kword_window_hits(masks: jax.Array, active: jax.Array,
                      bands: jax.Array) -> jax.Array:
    """Combine per-group delta masks into the K-word match bit.

    masks: [G, N, Pa] int32 delta masks (one per constraint group, from
    `banded_delta_mask_rows`); active: [G, N] bool (dead groups never
    constrain); bands: [N] int32 window W per row.  Returns bool [N, Pa]:
    anchor i matches iff some window start t in [0, W] intersects EVERY
    active group's mask in bits [t, t + W] — i.e. all K words fit inside
    one (W + 1)-wide window containing the anchor."""
    t_ok = None
    for g in range(masks.shape[0]):
        bits = delta_mask_t_bits(masks[g], bands)
        bits = jnp.where(active[g][:, None], bits, jnp.int32(-1))
        t_ok = bits if t_ok is None else (t_ok & bits)
    if t_ok is None:
        return jnp.zeros(masks.shape[1:], jnp.bool_)
    return t_ok != 0


def banded_min_delta_rows(a: jax.Array, b_key_sorted: jax.Array,
                          b_delta: jax.Array, bands: jax.Array, *,
                          implementation: str = "pallas",
                          interpret: bool | None = None, block_a: int = TILE,
                          block_b: int = TILE) -> jax.Array:
    """Batched banded min-delta (the proximity-scoring twin of
    `banded_intersect_rows`): out[n, i] = min over j with
    |a[n, i] - b_key[n, j]| <= bands[n] of (|a[n, i] - b_key[n, j]| +
    b_delta[n, j]), or I32_SENTINEL when no such j — so `< I32_SENTINEL` is
    exactly the banded-membership bit and the value feeds w(d) = 1/(1+d).

    b rows must be sorted by (key, delta) — the composite order the batch
    executor sorts into — and, per plan construction, rows with bands[n] > 0
    carry all-zero deltas (dist-carrying fetches are always band-0): the
    two-probe ref path is exact exactly on that domain, while the Pallas
    dense-tile path computes the general min.  deltas in [0, 15]
    (SCORE_DELTA_BITS); I32_SENTINEL entries of `a` never match.
    """
    assert a.dtype == jnp.int32 and b_key_sorted.dtype == jnp.int32
    N, pa = a.shape
    pb = b_key_sorted.shape[1]
    if implementation == "ref":
        pad = jnp.int64(1) << 40
        comp = jnp.where(b_key_sorted == I32_SENTINEL, pad,
                         (b_key_sorted.astype(jnp.int64) << _SDB)
                         | b_delta.astype(jnp.int64))
        probe = jnp.where(a == I32_SENTINEL, pad, a.astype(jnp.int64) << _SDB)

        def row(cv, pv, band):
            idx = jnp.searchsorted(cv, pv, side="left")
            hi = jnp.clip(idx, 0, pb - 1)
            lo = jnp.clip(idx - 1, 0, pb - 1)
            e_hi, e_lo = cv[hi], cv[lo]
            a_key = pv >> _SDB
            kd_hi = (e_hi >> _SDB) - a_key
            kd_lo = a_key - (e_lo >> _SDB)
            ok_hi = (idx < pb) & (kd_hi <= band)
            ok_lo = (idx > 0) & (kd_lo <= band)
            big = jnp.int32(I32_SENTINEL)
            mask = jnp.int64((1 << _SDB) - 1)
            c_hi = jnp.where(ok_hi, kd_hi.astype(jnp.int32)
                             + (e_hi & mask).astype(jnp.int32), big)
            c_lo = jnp.where(ok_lo, kd_lo.astype(jnp.int32)
                             + (e_lo & mask).astype(jnp.int32), big)
            return jnp.minimum(c_hi, c_lo)

        out = jax.vmap(row)(comp, probe, bands.astype(jnp.int64))
        return jnp.where(a == I32_SENTINEL, I32_SENTINEL, out)

    if N == 0 or pa == 0 or pb == 0:
        return jnp.full((N, pa), I32_SENTINEL, jnp.int32)
    out = _banded_rows("min_delta", a,
                       [b_key_sorted, b_delta.astype(jnp.int32)], bands,
                       block_a, block_b, interpret)
    return jnp.where(a == I32_SENTINEL, I32_SENTINEL, out)


# ---------------------------------------------------------------------------
# embedding bag
# ---------------------------------------------------------------------------

def segment_bag(table: jax.Array, ids: jax.Array, weights: jax.Array | None = None,
                combine: str = "sum", *, implementation: str = "pallas",
                interpret: bool | None = None) -> jax.Array:
    """EmbeddingBag(table, ids) -> [B, D]; ids [B, F] int32, -1 = pad."""
    if implementation == "ref":
        return ref.segment_bag_ref(table, ids, weights, combine)
    B, F = ids.shape
    w = weights if weights is not None else jnp.ones((B, F), table.dtype)
    out = segment_bag_pallas(table, ids.astype(jnp.int32), w.astype(table.dtype),
                             interpret=_interp(interpret))   # fp32 accumulator
    if combine == "mean":
        denom = jnp.maximum((ids >= 0).sum(axis=1, keepdims=True), 1).astype(jnp.float32)
        out = out / denom
    return out.astype(table.dtype)


# ---------------------------------------------------------------------------
# flash prefill attention
# ---------------------------------------------------------------------------

def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  block_q: int = 512, block_kv: int = 512,
                  implementation: str = "pallas",
                  interpret: bool | None = None) -> jax.Array:
    """Causal GQA prefill.  q: [B, S, Hq, D]; k, v: [B, S, Hkv, D].

    The Pallas path keeps each (block_q x block_kv) score tile in VMEM
    (the §Roofline fix for the prefill memory term)."""
    if implementation == "ref":
        return ref.flash_prefill_ref(q, k, v)
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    bq = min(block_q, S)
    bkv = min(block_kv, S)
    # rows ordered (q_block, g, q_within) per KV head — see flash_prefill.py
    q6 = q.reshape(B, S // bq, bq, Hkv, G, D).transpose(0, 3, 1, 4, 2, 5)
    q5 = q6.reshape(B, Hkv, S * G, D)
    out5 = flash_prefill_pallas(q5, k, v, block_q=bq, block_kv=bkv,
                                interpret=_interp(interpret))
    out = out5.reshape(B, Hkv, S // bq, G, bq, D).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, S, Hq, D)


# ---------------------------------------------------------------------------
# flash decode attention
# ---------------------------------------------------------------------------

def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_len: jax.Array | int, *, block_s: int = 512,
                 implementation: str = "pallas",
                 interpret: bool | None = None) -> jax.Array:
    """q: [B, Hq, D]; k, v: [B, S, Hkv, D]; kv_len: [B] or scalar."""
    if implementation == "ref":
        return ref.flash_decode_ref(q, k, v, kv_len)
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len, jnp.int32)
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        zeros = jnp.zeros((B, pad, Hkv, D), k.dtype)
        k = jnp.concatenate([k, zeros], axis=1)
        v = jnp.concatenate([v, zeros], axis=1)
    q4 = q.reshape(B, Hkv, G, D)
    out = flash_decode_pallas(q4, k, v, kv_len, block_s=bs,
                              interpret=_interp(interpret))
    return out.reshape(B, Hq, D)
