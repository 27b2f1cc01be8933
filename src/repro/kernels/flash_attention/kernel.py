"""FlashAttention-2 style Pallas TPU kernel (causal, GQA).

Tiling: grid (batch, q_head, q_blocks, kv_blocks) with the KV axis
innermost; the online-softmax state (m, l, acc) lives in VMEM scratch and
persists across the KV sweep of one (b, h, iq) cell.  Q/K/V blocks are
(block_q x head_dim) / (block_k x head_dim) VMEM tiles; head_dim and the
block sizes are kept multiples of 128 on the lane axis so the MXU sees
aligned operands.  GQA maps q-head h to kv-head h // group via the K/V
BlockSpec index maps -- no KV duplication in HBM or VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_k: int, sm_scale: float, causal: bool,
               q_offset: int, n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                              # [bq, bk]
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + q_offset
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    m_ref[...] = m_cur
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q [B,Sq,H,hd]; k/v [B,Skv,KV,hd] -> [B,Sq,H,hd]."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    n_q, n_kv = sq // block_q, skv // block_k
    # layout: heads as leading grid dims -> [B,H,S,hd]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, n_q, n_kv)
    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k,
        sm_scale=1.0 / (hd ** 0.5), causal=causal, q_offset=q_offset,
        n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
