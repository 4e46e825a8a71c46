"""Pallas bit-unpack for the packed postings store (core/postings.py).

The packed arena stores each posting column as per-block anchors + bit-packed
deltas in width classes that divide the 32-bit lane, so a value never
straddles lane words and decode is branch-free VPU math:

    value = anchor + ((word >> shift) & mask(width))

The executors gather the lane words / per-block metadata with a plain XLA
gather (ops.unpack_postings) and hand this kernel the *dense, aligned*
(word, shift, width, anchor) planes — the dense-compute twin of the banded
intersect kernels next door, fusing the whole unpack of a gathered slab into
one elementwise pass.  Arithmetic right shift is safe: a packed value at bit
`shift` has width ≤ 32 - shift (widths divide 32), so the sign-extension
bits land above the mask; width 32 uses the all-ones mask and reproduces the
word itself.  Values are exact modulo 2**32, i.e. bit-exact for every int32
posting column.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
MAX_BLOCK_ROWS = 512         # (512, 128) int32 = 256 KiB per plane


def _kernel(words_ref, shift_ref, width_ref, anchor_ref, o_ref):
    w = width_ref[...]
    # width 32 -> all-ones; the (1 << w) - 1 branch is only selected for
    # w <= 16 (the clamp keeps the unselected branch's shift in-range)
    mask = jnp.where(w >= 32, jnp.int32(-1),
                     (jnp.int32(1) << jnp.minimum(w, 31)) - 1)
    val = (words_ref[...] >> shift_ref[...]) & mask
    o_ref[...] = anchor_ref[...] + val


def unpack_fields_pallas(words: jax.Array, shifts: jax.Array,
                         widths: jax.Array, anchors: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """anchor + ((words >> shifts) & mask(widths)), elementwise int32.

    All inputs [n] int32; widths in core.postings.PACK_WIDTHS.  The planes
    are padded to whole (rows, 128) blocks, rows a multiple of 8."""
    n = words.shape[0]
    rows = -(-max(n, 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    block_rows = min(rows, MAX_BLOCK_ROWS)
    rows = -(-rows // block_rows) * block_rows

    def prep(x):
        return jnp.pad(x, (0, rows * LANES - n)).reshape(rows, LANES)

    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, jnp.int32(0)))
    fn = pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
        name="unpack",
    )
    out = fn(prep(words), prep(shifts), prep(widths), prep(anchors))
    return out.reshape(-1)[:n]
