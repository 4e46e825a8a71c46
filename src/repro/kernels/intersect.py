"""Banded sorted-set intersection — the search engine's hot kernel.

TPU adaptation of posting-list merge (DESIGN.md §2): instead of pointer
chasing, both key lists are tiled; for each tile of `a` only the `b` tiles
whose value range can overlap [a_min - band, a_max + band] are DMA'd into
VMEM (tile bounds are scalar-prefetched, so the BlockSpec index map skips
non-overlapping tiles entirely — the TPU analogue of galloping).  Inside a
tile pair the membership test is a dense compare on the VPU: the b tile is
rotated through every (sublane, lane) alignment against the resident a
tile (`_fold_pairs`), so each a element meets each b element exactly once
with whole-vreg elementwise ops — branch-free, no relayout, O(matching-band)
tile fetches overall.

Keys are *compact per-shard* int32 (doc_local << pos_bits | pos): TPU vector
units have no native int64 lane type, so the batched executor's global
63-bit keys are re-based against each row's own doc-shard base before
hitting this kernel (ops.py).  Everything inside the kernels and their index
maps is int32, also under the package-wide x64 flag.  Rows arrive
shard-segmented (batch_executor._build_rows): every (a, b, band) row pair
holds exactly one doc shard's postings, for both the engine's jit'd bucket
step and the serve tier's shard_map'd step — the kernel itself never sees a
shard loop.

band = 0  -> exact membership (precise phrase matching via shifted keys)
band = W  -> positional window join (word-set-with-distance queries)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES      # one int32 vreg: the smallest legal block
I32_SENTINEL = jnp.iinfo(jnp.int32).max


def _loop(n: int, body, init):
    """fori_loop with an int32 counter (python bounds would be int64 under
    x64, which Mosaic cannot lower)."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, init)


def _fold_pairs(a_ref, b_refs, o_ref, step):
    """o = step-fold of every (a element, b element) pair of one tile pair.

    a_ref/o_ref: (RA, 128); b_refs: aligned (RB, 128) planes (key, and the
    score delta for the min-delta kernel); RA, RB multiples of 8.  Each
    (8, 128) b sub-tile is stacked to RA rows and rotated through all 8
    sublane and 128 lane offsets, so a[r, l] meets every b element of the
    sub-tile exactly once.  `step(acc, a, *b)` is elementwise on (RA, 128)
    int32 and must be an order-free accumulation (OR / min)."""
    ra, rb = a_ref.shape[0], b_refs[0].shape[0]
    a = a_ref[...]

    def lane_step(_, carry):
        acc, bs = carry
        return step(acc, a, *bs), tuple(pltpu.roll(b, jnp.int32(1), 1)
                                        for b in bs)

    def sublane_step(_, carry):
        acc, bs = _loop(LANES, lane_step, carry)   # lanes back in place
        return acc, tuple(pltpu.roll(b, jnp.int32(1), 0) for b in bs)

    def subtile_step(j, acc):
        r0 = pl.multiple_of(j * SUBLANES, SUBLANES)
        bs = tuple(jnp.tile(ref[pl.ds(r0, SUBLANES), :],
                            (ra // SUBLANES, 1)) for ref in b_refs)
        return _loop(SUBLANES, sublane_step, (acc, bs))[0]

    o_ref[...] = _loop(rb // SUBLANES, subtile_step, o_ref[...])


def _hit_step(band):
    def step(acc, a, b):
        return acc | jnp.where(jnp.abs(a - b) <= band, jnp.int32(1),
                               jnp.int32(0))
    return step


def _min_delta_step(band):
    def step(acc, a, bk, bd):
        kd = jnp.abs(a - bk)
        return jnp.minimum(acc, jnp.where(kd <= band, kd + bd,
                                         jnp.int32(I32_SENTINEL)))
    return step


def _delta_mask_step(band):
    def step(acc, a, b):
        d = b - a
        bit = jnp.int32(1) << jnp.clip(d + band, jnp.int32(0), jnp.int32(31))
        return acc | jnp.where(jnp.abs(d) <= band, bit, jnp.int32(0))
    return step


def _rows_kernel(step_for, init):
    """Kernel body shared by the three banded twins: zero/sentinel-init the
    output block at the first b tile, then fold every visited b tile.  The
    band is scalar-prefetched per a-block, so one pallas_call serves both the
    single-list op (constant band broadcast over blocks) and a whole batch
    of independent (a, b, band) row pairs (the batch executor's layout: each
    row = one fetch-group test, bands mixing 0 (phrase) and W (window))."""
    def kernel(lo_ref, nt_ref, band_ref, a_ref, *refs):
        b_refs, o_ref = refs[:-1], refs[-1]
        i = pl.program_id(0)
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.full(o_ref.shape, init, jnp.int32)

        @pl.when(k < nt_ref[i])
        def _compute():
            _fold_pairs(a_ref, b_refs, o_ref, step_for(band_ref[i]))
    return kernel


def _rows_call(kernel, a2d, b_planes, lo_tiles, n_tiles, bands, *, name,
               block_a, block_b, max_tiles, interpret):
    """pallas_call `name` over (a-block, visited b tile).  Blocks are whole
    (8k, 128) int32 tiles.  The b index map walks lo .. lo + n - 1 and then
    holds the last visited block, so skipped steps issue no new DMA."""
    assert block_a % TILE == 0 and block_b % TILE == 0, (block_a, block_b)
    ra, rb = block_a // LANES, block_b // LANES

    def a_map(i, k, lo, nt, bd):
        return i, jnp.int32(0)

    def b_map(i, k, lo, nt, bd):
        return lo[i] + jnp.minimum(k, jnp.maximum(nt[i] - 1, 0)), jnp.int32(0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(a2d.shape[0] // ra, max_tiles),
        in_specs=[pl.BlockSpec((ra, LANES), a_map)]
        + [pl.BlockSpec((rb, LANES), b_map) for _ in b_planes],
        out_specs=pl.BlockSpec((ra, LANES), a_map),
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(a2d.shape, jnp.int32),
        interpret=interpret,
        name=name,
    )
    return fn(lo_tiles, n_tiles, bands, a2d, *b_planes)


def banded_intersect_rows_pallas(a2d: jax.Array, b2d: jax.Array,
                                 lo_tiles: jax.Array, n_tiles: jax.Array,
                                 bands: jax.Array, *, block_a: int,
                                 block_b: int, max_tiles: int,
                                 interpret: bool) -> jax.Array:
    """Raw pallas_call for batched rows (a2d/b2d: [R, 128] int32; b sorted
    within each logical row): out = 1 where some b lies within the row's
    band of a.

    lo_tiles/n_tiles/bands are per-a-block: first b-block index (absolute,
    i.e. already offset to the owning row's b segment), number of b blocks to
    visit, and the row's band width (see ops.banded_intersect_rows)."""
    return _rows_call(_rows_kernel(_hit_step, 0), a2d, (b2d,), lo_tiles,
                      n_tiles, bands, name="intersect", block_a=block_a,
                      block_b=block_b, max_tiles=max_tiles,
                      interpret=interpret)


def banded_min_delta_rows_pallas(a2d: jax.Array, bk2d: jax.Array,
                                 bd2d: jax.Array, lo_tiles: jax.Array,
                                 n_tiles: jax.Array, bands: jax.Array, *,
                                 block_a: int, block_b: int, max_tiles: int,
                                 interpret: bool) -> jax.Array:
    """Scoring twin (proximity relevance, api.py): for each a element, the
    MINIMUM over in-band b of (|a - b_key| + b_delta) — key distance plus
    the posting's stored slot delta — accumulated as an int32 min across
    the visited b tiles.  I32_SENTINEL = no in-band b (the membership bit
    and the score read the same output).  Layout as
    banded_intersect_rows_pallas, plus the aligned b_delta planes."""
    return _rows_call(_rows_kernel(_min_delta_step, I32_SENTINEL), a2d,
                      (bk2d, bd2d), lo_tiles, n_tiles, bands,
                      name="min_delta", block_a=block_a, block_b=block_b,
                      max_tiles=max_tiles, interpret=interpret)


def banded_delta_mask_rows_pallas(a2d: jax.Array, b2d: jax.Array,
                                  lo_tiles: jax.Array, n_tiles: jax.Array,
                                  bands: jax.Array, *, block_a: int,
                                  block_b: int, max_tiles: int,
                                  interpret: bool) -> jax.Array:
    """K-word join twin (kword mode, core/kword.py): for each a element, a
    bitmask over the signed delta d = b - a of the in-band b's — bit
    (d + band) set iff some b sits exactly at a + d.  The caller AND-combines
    per-group window scans of these masks to decide whether all K words of a
    query fit one window (ops.banded_delta_mask_rows).  band <= 15 so every
    bit index (d + band) <= 30 fits an int32 lane.  Layout as
    banded_intersect_rows_pallas."""
    return _rows_call(_rows_kernel(_delta_mask_step, 0), a2d, (b2d,),
                      lo_tiles, n_tiles, bands, name="delta_mask",
                      block_a=block_a, block_b=block_b, max_tiles=max_tiles,
                      interpret=interpret)
