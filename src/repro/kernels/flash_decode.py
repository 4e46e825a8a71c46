"""Flash-decode attention kernel: one new token vs a long KV cache.

The LM zoo's serving hot spot (decode_32k / long_500k cells): per decoded
token the work is a [G, D] x [S, D] stream over the cache — memory-bound, so
the kernel tiles S into VMEM-sized blocks and keeps the online-softmax
running state (m, l, acc) in VMEM scratch across grid steps (FlashAttention
recurrence, adapted to TPU: the MXU sees (G, D) x (D, BS) matmuls, the VPU
does the rescaling).

GQA layout: q is pre-reshaped to [B, Hkv, G, D] so one grid step serves the
whole query-head group of one KV head — k/v rows are fetched once per group,
not once per query head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, scale: float):
    b = pl.program_id(0)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[b]

    @pl.when(kb * block_s < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)         # (BS, D)
        v = v_ref[0, :, 0].astype(jnp.float32)         # (BS, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (G, BS)
        span = kb * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(span < kv_len, s, NEG_INF)

        m_old = m_ref[...]                             # (G, 128) replicated
        m_blk = jnp.max(s, axis=1, keepdims=True)      # (G, 1)
        m_new = jnp.maximum(m_old, jnp.broadcast_to(m_blk, m_old.shape))
        alpha = jnp.exp(m_old - m_new)                 # (G, 128)
        p = jnp.exp(s - m_new[:, :1])                  # (G, BS)
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_old.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode_pallas(q4: jax.Array, k: jax.Array, v: jax.Array,
                        kv_len: jax.Array, *, block_s: int = 512,
                        interpret: bool) -> jax.Array:
    """q4: [B, Hkv, G, D]; k, v: [B, S, Hkv, D]; kv_len: [B] int32.

    Returns [B, Hkv, G, D] in q4.dtype.  S must be a multiple of block_s.
    """
    B, Hkv, G, D = q4.shape
    S = k.shape[1]
    assert S % block_s == 0, (S, block_s)
    scale = 1.0 / (D ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, S // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, kb, kvlen: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, kb, kvlen: (b, kb, h, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, kb, kvlen: (b, kb, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, kb, kvlen: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),   # m (running max, lane-replicated)
            pltpu.VMEM((G, 128), jnp.float32),   # l (running denominator)
            pltpu.VMEM((G, D), jnp.float32),     # acc (unnormalized output)
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q4.dtype),
        interpret=interpret,
    )
    return fn(kv_len, q4, k, v)
