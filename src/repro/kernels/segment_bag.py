"""EmbeddingBag gather-reduce kernel (recsys hot path; kernel_taxonomy §B.6).

JAX has no native EmbeddingBag; the framework substrate implements it as
take + segment_sum (ref.py).  On TPU the lookup is DMA-bound, so the Pallas
kernel drives the table-row DMA directly from *scalar-prefetched* ids: the
BlockSpec index map reads ids[b, f] and fetches exactly that row block into
VMEM per grid step — the TPU analogue of FBGEMM's table-batched embedding.

Padding ids (< 0) are clamped to row 0 and predicated off the accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(ids_ref, q_ref, w_ref, o_ref):
    b = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    valid = ids_ref[b, f] >= 0
    w = w_ref[0, f].astype(jnp.float32)
    row = q_ref[...].astype(jnp.float32)      # (1, D) — the ids[b, f] table row
    o_ref[...] += jnp.where(valid, w, 0.0) * row   # fp32 accumulation


def segment_bag_pallas(table: jax.Array, ids: jax.Array, weights: jax.Array,
                       *, interpret: bool) -> jax.Array:
    """table: [V, D]; ids: [B, F] int32 (-1 pad); weights: [B, F] table.dtype.

    Returns [B, D] weighted bag sums.  Mean combine is applied by the ops.py
    wrapper (divide by valid count) so the kernel stays a pure gather-MAC.
    """
    B, F = ids.shape
    V, D = table.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, F),
        in_specs=[
            # the table row selected by the prefetched id (clamped for pads)
            pl.BlockSpec((1, D), lambda b, f, ids: (jnp.maximum(ids[b, f], 0), 0)),
            pl.BlockSpec((1, F), lambda b, f, ids: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, f, ids: (b, 0)),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )
    return fn(ids, table, weights)
