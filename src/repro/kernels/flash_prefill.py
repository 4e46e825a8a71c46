"""Flash-attention prefill kernel: causal GQA attention with VMEM-resident
score blocks (the §Roofline fix for the prefill_32k memory term: the XLA
chunked path round-trips f32 score blocks through HBM; here a (block_q x
block_kv) tile lives only in VMEM).

Grid: (batch, kv-head, q-blocks, kv-blocks), kv innermost with the online-
softmax running state (m, l, acc) in VMEM scratch.  Causality is enforced
two ways: kv blocks strictly above the diagonal are skipped via pl.when
(compute predication), and the diagonal block gets the elementwise mask.
Layout matches flash_decode: q pre-reshaped [B, Hkv, G, S, D] so one grid
step serves a whole query-head group of one KV head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_kv: int, scale: float, n_groups: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    n_kb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv block strictly above the q block's diagonal -> skip
    @pl.when(kb * block_kv <= qb * block_q + block_q - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G*BQ, D) flattened
        k = k_ref[0, :, 0].astype(jnp.float32)         # (BKV, D)
        v = v_ref[0, :, 0].astype(jnp.float32)         # (BKV, D)
        G = n_groups
        BQ = block_q
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # rows are (g, q) pairs; causal mask on the q coordinate only
        row_q = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % BQ
        q_pos = qb * BQ + row_q
        k_pos = kb * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_old = m_ref[...]                             # (G*BQ, 128)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_old, jnp.broadcast_to(m_blk, m_old.shape))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_old.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_prefill_pallas(q5: jax.Array, k: jax.Array, v: jax.Array, *,
                         block_q: int = 512, block_kv: int = 512,
                         interpret: bool) -> jax.Array:
    """q5: [B, Hkv, G*S, D] (G query heads per KV head, flattened with S);
    k, v: [B, S, Hkv, D].  Returns [B, Hkv, G*S, D] in q5.dtype.

    S must divide by both block sizes.  The flattened (G, S) rows let the
    MXU see (G*BQ, D) x (D, BKV) matmuls.
    """
    B, Hkv, GS, D = q5.shape
    S = k.shape[1]
    G = GS // S
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    scale = 1.0 / (D ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B, Hkv, S // block_q, S // block_kv),
        in_specs=[
            # q rows for block qb: all G groups x the qb-th block of S
            pl.BlockSpec((1, 1, G * block_q, D),
                         lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, block_kv, 1, D),
                         lambda b, h, qb, kb: (b, kb, h, 0)),
            pl.BlockSpec((1, block_kv, 1, D),
                         lambda b, h, qb, kb: (b, kb, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G * block_q, D),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, 128), jnp.float32),   # m
            pltpu.VMEM((G * block_q, 128), jnp.float32),   # l
            pltpu.VMEM((G * block_q, D), jnp.float32),     # acc
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_kv=block_kv,
                          scale=scale, n_groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GS, D), q5.dtype),
        interpret=interpret,
    )
    return fn(q5, k, v)
