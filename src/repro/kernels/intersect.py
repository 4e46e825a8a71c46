"""Banded sorted-set intersection — the search engine's hot kernel.

TPU adaptation of posting-list merge (DESIGN.md §2): instead of pointer
chasing, the membership test is a dense compare on the VPU.  A resident
`b` block is rotated against a resident `a` block (`_fold_pairs`), so each
a element meets each b element of its row exactly once with whole-vreg
elementwise ops — branch-free, no relayout.  Two layouts, chosen by the
static row widths alone (`ops.packed_layout`):

* tiled (a row or a constraint row wider than 1024 elements): each logical
  row pads to whole (8, 128) int32 tiles; the b tile is rotated through all
  8 sublane and 128 lane alignments.  For each tile of `a` only the `b`
  tiles whose value range can overlap [a_min - band, a_max + band] are
  DMA'd into VMEM (tile bounds are scalar-prefetched, so the BlockSpec
  index map skips non-overlapping tiles entirely — the TPU analogue of
  galloping): O(matching-band) tile fetches, what long lists need.
* packed (both widths at most 1024 elements): a block holds `rows`
  logical rows, one per sublane of each vreg, and each row's 128-lane
  planes lie in consecutive (rows, 128) slabs.  The b planes are rotated
  through the 128 lane alignments only; a lane roll never moves a key to
  another sublane, so rows never meet each other's keys.  Bands arrive as
  an aligned per-row plane, and a scalar-prefetched flag skips blocks with
  no row holding real keys on both sides.  A row that pads to one 128-lane
  plane costs 128 / 8 = 16 vreg steps here against 1024 tiled.

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

Three twins share both layouts (`TWINS`), each pallas_call named after its
twin, with `_packed` appended for the packed layout:

* `intersect`: 1 where some b lies within the row's band of a.
* `min_delta` (proximity scoring, api.py): the MINIMUM over in-band b of
  (|a - b_key| + b_delta), I32_SENTINEL where no b is in band; b comes as
  aligned key and delta planes.
* `delta_mask` (K-word join, core/kword.py): a bitmask over the signed
  delta d = b - a of the in-band b's, bit (d + band) set iff some b sits
  exactly at a + d; band <= 15, so every bit index fits an int32 lane.
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
# lane rolls per packed loop iteration: on a TPU v5e 4 runs the packed
# kernel 15-30% faster than 1; 16 gains at most 10% more
_LANE_UNROLL = 4


def _loop(n: int, body, init):
    """fori_loop with an int32 counter (python bounds would be int64 under
    x64, which Mosaic cannot lower)."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, init)


def _fold_pairs(a_ref, b_refs, o_ref, step, *, plane: int = SUBLANES,
                packed: bool = False):
    """o = step-fold of every (a element, b element) pair of one block pair.

    a_ref/o_ref: (RA, 128); b_refs: aligned (RB, 128) planes (key, and the
    score delta for the min-delta kernel); RA, RB multiples of `plane`.
    Each (plane, 128) b slab is stacked to RA rows and rotated through the
    128 lane offsets, and, tiled (plane = 8), through the 8 sublane offsets
    too, so a[r, l] meets every b element of its row exactly once.
    `step(acc, a, *b)` is elementwise on (RA, 128) int32 and must be an
    order-free accumulation (OR / min)."""
    ra, rb = a_ref.shape[0], b_refs[0].shape[0]
    a = a_ref[...]

    def lane_step(_, carry):
        acc, bs = carry
        return step(acc, a, *bs), tuple(pltpu.roll(b, jnp.int32(1), 1)
                                        for b in bs)

    def lanes_step(i, carry):
        for _ in range(_LANE_UNROLL):
            carry = lane_step(i, carry)
        return carry

    def sublane_step(_, carry):
        acc, bs = _loop(LANES, lane_step, carry)   # lanes back in place
        return acc, tuple(pltpu.roll(b, jnp.int32(1), 0) for b in bs)

    def slab_step(j, acc):
        r0 = pl.multiple_of(j * plane, plane)
        bs = tuple(jnp.tile(ref[pl.ds(r0, plane), :], (ra // plane, 1))
                   for ref in b_refs)
        if packed:
            return _loop(LANES // _LANE_UNROLL, lanes_step, (acc, bs))[0]
        return _loop(SUBLANES, sublane_step, (acc, bs))[0]

    o_ref[...] = _loop(rb // plane, slab_step, o_ref[...])


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


# twin -> (step builder over a band, output's initial value); the band is a
# scalar in the tiled layout and a per-row plane in the packed one
TWINS = {"intersect": (_hit_step, 0),
         "min_delta": (_min_delta_step, I32_SENTINEL),
         "delta_mask": (_delta_mask_step, 0)}


def _rows_kernel(step_for, init):
    """Tiled kernel body: zero/sentinel-init the output block at the first
    b tile, then fold every visited b tile.  The band is scalar-prefetched
    per a-block, so one pallas_call serves both the single-list op and a
    whole batch of independent (a, b, band) row pairs (the batch executor's
    layout: each row = one fetch-group test, bands mixing 0 (phrase) and W
    (window))."""
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


def banded_rows_pallas(twin: str, a2d: jax.Array, b_planes, lo_tiles,
                       n_tiles, bands, *, block_a: int, block_b: int,
                       max_tiles: int, interpret: bool) -> jax.Array:
    """Tiled pallas_call `twin` over (a-block, visited b tile).  a2d and
    each b plane: [R, 128] int32, whole (8k, 128) tiles per logical row, b
    keys sorted within each row.  lo_tiles/n_tiles/bands are per-a-block:
    first b-block index (absolute, i.e. already offset to the owning row's
    b segment), number of b blocks to visit, and the row's band width (see
    ops._banded_rows).  The b index map walks lo .. lo + n - 1 and then
    holds the last visited block, so skipped steps issue no new DMA."""
    assert block_a % TILE == 0 and block_b % TILE == 0, (block_a, block_b)
    step_for, init = TWINS[twin]
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
        _rows_kernel(step_for, init),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(a2d.shape, jnp.int32),
        interpret=interpret,
        name=twin,
    )
    return fn(lo_tiles, n_tiles, bands, a2d, *b_planes)


def _packed_kernel(step_for, init, rows):
    """Packed kernel body: init the block's output, and, where the block's
    flag says some row holds real keys on both sides, fold every b plane
    against every a plane at once by lane rolls only."""
    def kernel(live_ref, a_ref, band_ref, *refs):
        b_refs, o_ref = refs[:-1], refs[-1]
        o_ref[...] = jnp.full(o_ref.shape, init, jnp.int32)

        @pl.when(live_ref[pl.program_id(0)] != 0)
        def _compute():
            band = jnp.tile(band_ref[...], (a_ref.shape[0] // rows, 1))
            _fold_pairs(a_ref, b_refs, o_ref, step_for(band), plane=rows,
                        packed=True)
    return kernel


def packed_rows_pallas(twin: str, a2d: jax.Array, b_planes, band2d, live, *,
                       rows: int, interpret: bool) -> jax.Array:
    """Packed pallas_call `twin + "_packed"`, one grid step per block of
    `rows` logical rows.  a2d: [n_blocks * sa * rows, 128] int32, block i's
    a plane p holding lanes [128 p, 128 p + 128) of its rows, one row per
    sublane; each b plane: [n_blocks * sb * rows, 128] the same way (keys
    sorted within each row); band2d: [n_blocks * rows, 128], each row's
    band across its lanes; live: [n_blocks] int32, 0 = skip the block
    (see ops._packed_rows)."""
    assert rows % SUBLANES == 0, rows
    step_for, init = TWINS[twin]
    n_blocks = live.shape[0]
    ra = a2d.shape[0] // n_blocks
    rb = b_planes[0].shape[0] // n_blocks

    def blk(i, lv):
        return i, jnp.int32(0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((ra, LANES), blk),
                  pl.BlockSpec((rows, LANES), blk)]
        + [pl.BlockSpec((rb, LANES), blk) for _ in b_planes],
        out_specs=pl.BlockSpec((ra, LANES), blk),
    )
    fn = pl.pallas_call(
        _packed_kernel(step_for, init, rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(a2d.shape, jnp.int32),
        interpret=interpret,
        name=twin + "_packed",
    )
    return fn(live, a2d, band2d, *b_planes)
