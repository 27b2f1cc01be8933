"""Decode attention Pallas TPU kernel: one query token vs a long KV cache.

TPU adaptation (vs a CUDA decode kernel that maps heads to warps): the
GQA q-head GROUP (g rows) x head_dim tile is the MXU's M x K operand and
the KV sequence is swept in (block_k x head_dim) VMEM tiles with an
online-softmax scratch carry -- the sweep is the memory-bound part and is
what the roofline's HBM term measures.  Valid-length masking comes from the
per-batch lengths, held whole in scalar memory (SMEM) and indexed by the
batch grid index, so padded cache tail blocks add no numerical effect.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, block_k: int, sm_scale: float, n_kv: int,
                k_scale_ref=None, v_scale_ref=None):
    bi = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # [g, hd]
    k = k_ref[0, 0].astype(jnp.float32)           # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    if k_scale_ref is not None:                   # int8 cache: in-VMEM
        k = k * k_scale_ref[0, 0].astype(jnp.float32)[:, None]
        v = v * v_scale_ref[0, 0].astype(jnp.float32)[:, None]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    kv_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(kv_pos < len_ref[bi], s, NEG_INF)
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


def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_len: jax.Array, *, block_k: int = 512,
                            k_scale: jax.Array = None,
                            v_scale: jax.Array = None,
                            interpret: bool = False) -> jax.Array:
    """q [B,1,H,hd]; k/v [B,S,KV,hd] (bf16, or int8 with per-token-head
    k_scale/v_scale [B,S,KV]); kv_len [B] -> [B,1,H,hd].

    int8 mode streams the quantized cache from HBM and dequantizes in
    VMEM -- the HBM traffic (the decode bottleneck) is halved."""
    b, _, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    block_k = min(block_k, s)
    assert s % block_k == 0
    n_kv = s // block_k
    int8 = k_scale is not None
    # group queries by kv head: [B, KV, g, hd]
    qg = q.reshape(b, kvh, g, hd)
    kt = k.transpose(0, 2, 1, 3)                  # [B, KV, S, hd]
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, kvh, n_kv)
    kernel = functools.partial(_dec_kernel, block_k=block_k,
                               sm_scale=1.0 / (hd ** 0.5), n_kv=n_kv)
    in_specs = [
        # whole [B] array: a (1,) SMEM block is refused by the TPU lowering
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, g, hd), lambda bi, ki, ii: (bi, ki, 0, 0)),
        pl.BlockSpec((1, 1, block_k, hd),
                     lambda bi, ki, ii: (bi, ki, ii, 0)),
        pl.BlockSpec((1, 1, block_k, hd),
                     lambda bi, ki, ii: (bi, ki, ii, 0)),
    ]
    args = [kv_len.astype(jnp.int32), qg, kt, vt]
    if int8:
        def _kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                    m_ref, l_ref, acc_ref):
            kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, k_scale_ref=ks_ref, v_scale_ref=vs_ref)
        scale_spec = pl.BlockSpec((1, 1, block_k),
                                  lambda bi, ki, ii: (bi, ki, ii))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale.transpose(0, 2, 1), v_scale.transpose(0, 2, 1)]
        body = _kernel
        out_dtype = jnp.bfloat16
    else:
        body = kernel
        out_dtype = q.dtype
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, ki, ii: (bi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out.reshape(b, 1, h, hd).astype(q.dtype)
