"""Decode attention Pallas TPU kernel: one query token vs a long KV cache.

The kernel reads K/V straight out of the layer-stacked decode cache
[L,B,S,KV,hd]: the layer index is a scalar-prefetch operand (SMEM) that
the K/V index maps use, so no one-layer slice and no head-major copy of
the cache is materialised in front of the call.  The contiguous
[S,KV,hd] rows of one (layer, batch row) are viewed as [S*KV, hd] (a free
bitcast) and swept in (block_k*KV, hd) tiles holding every KV head of
block_k positions, with an online-softmax scratch carry -- the sweep is
the memory-bound part and is what the roofline's HBM term measures.

Heads are split inside VMEM: all H query heads score the whole tile in
one [H, hd] x [block_k*KV, hd]^T matmul and the columns of other KV heads
are masked to -inf before the softmax, so they add nothing to the
weights or to the value matmul.  The extra MXU rows cost far less than
the tile's HBM read.  Valid-length masking comes from the per-batch
lengths, also held in SMEM, so padded cache tail blocks add no numerical
effect.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
# K and V tiles of this many bytes each: one tile is a few microseconds
# of HBM time, long enough to hide a grid step's fixed cost.
TILE_BYTES = 1 << 20


def _mix(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [H, n] f32 @ v [n, hd] with f32 accumulation.  A bf16 v takes
    p as a bf16 head plus a bf16 remainder, two one-pass matmuls that
    keep p to about 16 bits."""
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if v.dtype == jnp.float32:
        return dot(p, v)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return dot(hi, v) + dot(lo, v)


def _dec_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, *refs,
                block_k: int, g: int, sm_scale: float, n_kv: int,
                int8: bool):
    del layer_ref                                 # used by the index maps
    if int8:
        k_scale_ref, v_scale_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                # [H, hd]
    k = k_ref[...]                                # [bk*KV, hd]
    v = v_ref[...]
    if int8:                                      # dequantize in VMEM
        k = k.astype(jnp.float32) * k_scale_ref[...].astype(jnp.float32)
        v = v.astype(jnp.float32) * v_scale_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    # column c holds position ik*block_k + c // KV of KV head c % KV;
    # query head r reads KV head r // g
    kvh = k.shape[0] // block_k
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    first = jax.lax.rem(col, kvh) * g
    keep = ((row >= first) & (row < first + g)
            & (col < (len_ref[bi] - ik * block_k) * kvh))
    s = jnp.where(keep, s, NEG_INF)
    m_prev = m_ref[...]                           # [H, 1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_cur
    acc_ref[...] = acc_ref[...] * alpha + _mix(p, v)

    @pl.when(ik == n_kv - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _block_k(s: int, kvh: int, hd: int, dtype) -> int:
    """S, halved while it is even and its K tile of every KV head is
    larger than TILE_BYTES."""
    per_pos = kvh * hd * jnp.dtype(dtype).itemsize
    block_k = s
    while block_k * per_pos > TILE_BYTES and block_k % 2 == 0:
        block_k //= 2
    return block_k


def decode_attention_stacked_kernel(
        q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array,
        layer, *, block_k: int = None, k_scale: jax.Array = None,
        v_scale: jax.Array = None, interpret: bool = False) -> jax.Array:
    """q [B,1,H,hd]; k/v [L,B,S,KV,hd] stacked over layers (bf16, or int8
    with per-token-head k_scale/v_scale [L,B,S,KV]); kv_len [B]; layer a
    scalar, static or traced -> [B,1,H,hd] attending over layer
    ``layer``'s K/V.  The other layers are never read."""
    b, _, h, hd = q.shape
    n_layers, _, s, kvh, _ = k.shape
    g = h // kvh
    if block_k is None:
        block_k = _block_k(s, kvh, hd, k.dtype)
    block_k = min(block_k, s)
    assert s % block_k == 0
    n_kv = s // block_k
    rows = block_k * kvh
    int8 = k_scale is not None
    # [S,KV,hd] rows are contiguous: viewing them as [S*KV, hd] is free
    flat = lambda x: x.reshape(n_layers, b, s * kvh, *x.shape[4:])
    at_layer = lambda bi, ik, layer_ref, len_ref: (layer_ref[0], bi, ik, 0)
    kv_spec = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), rows, hd),
                           at_layer)
    row_spec = pl.BlockSpec((pl.Squeezed(), h, hd),
                            lambda bi, ik, layer_ref, len_ref: (bi, 0, 0))
    in_specs = [row_spec, kv_spec, kv_spec]
    args = [q.reshape(b, h, hd), flat(k), flat(v)]
    if int8:
        # one scale per row of the tile, as a column
        scale_spec = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), rows, 1),
                                  at_layer)
        in_specs += [scale_spec, scale_spec]
        args += [flat(k_scale[..., None]), flat(v_scale[..., None])]
    kernel = functools.partial(_dec_kernel, block_k=block_k, g=g,
                               sm_scale=1.0 / (hd ** 0.5), n_kv=n_kv,
                               int8=int8)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_kv),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(
            (b, h, hd), jnp.bfloat16 if int8 else q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), kv_len.astype(jnp.int32),
      *args)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_len: jax.Array, *, block_k: int = None,
                            k_scale: jax.Array = None,
                            v_scale: jax.Array = None,
                            interpret: bool = False) -> jax.Array:
    """q [B,1,H,hd]; k/v [B,S,KV,hd] (bf16, or int8 with per-token-head
    k_scale/v_scale [B,S,KV]); kv_len [B] -> [B,1,H,hd]: the one-layer
    stack of ``decode_attention_stacked_kernel``.

    int8 mode streams the quantized cache from HBM and dequantizes in
    VMEM -- the HBM traffic (the decode bottleneck) is halved."""
    stack = lambda x: None if x is None else x[None]
    return decode_attention_stacked_kernel(
        q, stack(k), stack(v), kv_len, 0, block_k=block_k,
        k_scale=stack(k_scale), v_scale=stack(v_scale), interpret=interpret)
