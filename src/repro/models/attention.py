"""GQA/MHA/MQA self-attention and VLM cross-attention (pure jnp core).

The Pallas kernels in ``repro.kernels`` implement the same math for TPU; the
model switches via ``cfg.use_pallas``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import ops


def gqa_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool, q_offset=0, block_q: int = 512,
                kv_valid: Optional[jax.Array] = None,
                unroll: bool = False) -> jax.Array:
    """Memory-blocked attention (jnp flash-style): scans q blocks so the
    score matrix never materializes beyond [B, KV, G, block_q, Skv].
    Required for the 32k/500k cells where full S^2 scores would OOM.
    Queries are zero-padded up to a block multiple and sliced back."""
    sq_in = q.shape[1]
    block_q = min(block_q, sq_in)
    q = jnp.pad(q, ((0, 0), (0, -sq_in % block_q), (0, 0), (0, 0)))
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    n_blocks = sq // block_q
    qg = q.reshape(b, n_blocks, block_q, kvh, g, hd).transpose(
        1, 0, 2, 3, 4, 5)                       # [n, B, bq, KV, G, hd]
    kf = k       # bf16 operands; f32 accumulation via preferred dtype
    vf = v
    kv_pos = jnp.arange(skv)[None, :]

    from repro.distributed import context as dist_ctx

    @jax.checkpoint
    def body(_, inp):
        # rematted: without this, the scan transpose saves every block's
        # [B,KV,G,bq,Skv] scores -- the full S^2 matrix in aggregate.
        q_blk, idx = inp
        q_blk = dist_ctx.constrain_batch(q_blk)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, kf,
                            preferred_element_type=jnp.float32) \
            / jnp.sqrt(float(hd))
        if causal:
            q_pos = idx * block_q + jnp.arange(block_q)[:, None] + q_offset
            scores = jnp.where((kv_pos <= q_pos)[None, None, None],
                               scores, -1e30)
        if kv_valid is not None:
            scores = jnp.where(kv_valid[:, None, None, None, :],
                               scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(vf.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w, vf,
                         preferred_element_type=jnp.float32)
        return None, dist_ctx.constrain_batch(out.astype(q.dtype))

    _, outs = jax.lax.scan(body, None, (qg, jnp.arange(n_blocks)),
                           unroll=True if unroll else 1)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hd)
    return out[:, :sq_in].astype(q.dtype)


def quantize_kv(x: jax.Array):
    """[B,S,KV,hd] -> (int8 values, bf16 scales [B,S,KV]) per token+head."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.bfloat16) * scale[..., None].astype(jnp.bfloat16)


def gqa_core(q: jax.Array, k: jax.Array, v: jax.Array,
             mask: Optional[jax.Array]) -> jax.Array:
    """Grouped-query attention.

    q: [B, Sq, H, hd];  k/v: [B, Skv, KV, hd];  mask: [B, Sq, Skv] or None
    returns [B, Sq, H, hd].
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _maybe_pallas_prefill(cfg, q, k, v, q_offset):
    if not cfg.use_pallas:
        return None
    from repro.kernels.flash_attention import ops as fa_ops
    return fa_ops.flash_attention(q, k, v, causal=True, q_offset=q_offset)


def _maybe_pallas_decode(cfg, q, k, v, kv_len, layer):
    """k/v the layer-stacked cache [L,B,S,KV,hd]: the kernel reads
    ``layer`` in place."""
    if not cfg.use_pallas:
        return None
    from repro.kernels.decode_attention import ops as da_ops
    return da_ops.decode_attention(q, k, v, kv_len, layer)


def _expand_kv_for_tp(cfg: ModelConfig, k: jax.Array, v: jax.Array):
    """When KV heads don't divide the TP axis but H does, broadcast K/V to
    full H so every attention tensor shards cleanly over "model".  The
    per-device expanded slice (H/tp heads) is SMALLER than a replicated
    un-expanded K/V, and compute stops being replicated across the axis."""
    from repro.distributed import context as dist_ctx
    tp = dist_ctx.tp_size()
    kvh = k.shape[2]
    if tp == 1 or kvh % tp == 0 or cfg.n_heads % tp != 0:
        return k, v
    g = cfg.n_heads // kvh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    return (dist_ctx.constrain_heads(k), dist_ctx.constrain_heads(v))


def project_qkv(p: Dict, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array,
                                               jax.Array]:
    """x [B,S,d] -> q [B,S,H,hd], k/v [B,S,KV,hd] (RoPE + optional qk-norm)."""
    from repro.distributed import context as dist_ctx
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = ops.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = ops.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = ops.apply_rope(q, positions, cfg.rope_theta)
    k = ops.apply_rope(k, positions, cfg.rope_theta)
    q = dist_ctx.constrain_heads(q)
    return q, k, v


_BLOCKED_THRESHOLD = 1024


def _causal_attn(cfg: ModelConfig, q, k, v):
    out = _maybe_pallas_prefill(cfg, q, k, v, 0)
    if out is not None:
        return out
    k, v = _expand_kv_for_tp(cfg, k, v)
    if q.shape[1] > _BLOCKED_THRESHOLD:
        return gqa_blocked(q, k, v, causal=True, unroll=cfg.scan_unroll)
    mask = ops.causal_mask(q.shape[1], k.shape[1], 0)[None]
    return gqa_core(q, k, v, mask)


def self_attention_train(p: Dict, cfg: ModelConfig, x: jax.Array,
                         positions: jax.Array) -> jax.Array:
    """Full-sequence causal attention (training / no cache)."""
    q, k, v = project_qkv(p, cfg, x, positions)
    out = _causal_attn(cfg, q, k, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def self_attention_prefill(p: Dict, cfg: ModelConfig, x: jax.Array,
                           positions: jax.Array, cache_len: int):
    """Prefill: returns (out, (k_cache_entry, v_cache_entry)) padded to
    cache_len along the sequence axis."""
    q, k, v = project_qkv(p, cfg, x, positions)
    out = _causal_attn(cfg, q, k, v)
    from repro.models.model import cache_kv_heads
    if cache_kv_heads(cfg) != k.shape[2]:
        k, v = _expand_kv_for_tp(cfg, k, v)
    pad = cache_len - k.shape[1]
    if pad > 0:
        pads = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, pads)
        v = jnp.pad(v, pads)
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return proj, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return proj, {"k": k, "v": v}


def self_attention_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
                          position: jax.Array, cache: Dict, layer):
    """Single-token decode.  x [B,1,d]; position [B] absolute position of the
    new token; cache = {k [L,B,S,KV,hd], v [L,B,S,KV,hd]} (plus k_scale,
    v_scale [L,B,S,KV] for int8) stacked over the layers, of which this is
    ``layer``.  Returns (out, cache with the new K/V stored in place)."""
    s_max = cache["k"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = ops.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k_new = ops.rmsnorm(k_new, p["k_norm"], cfg.norm_eps)
    pos = position[:, None]                     # [B,1]
    q = ops.apply_rope(q, pos, cfg.rope_theta)
    k_new = ops.apply_rope(k_new, pos, cfg.rope_theta)
    if cache["k"].shape[3] != k_new.shape[2]:   # expanded cache layout
        k_new, v_new = _expand_kv_for_tp(cfg, k_new, v_new)
    from repro.distributed import context as dist_ctx
    q = dist_ctx.constrain_heads(q)
    int8_cache = cfg.kv_cache_dtype == "int8"
    new = {"k": k_new, "v": v_new}
    if int8_cache:
        new["k"], new["k_scale"] = quantize_kv(k_new)
        new["v"], new["v_scale"] = quantize_kv(v_new)
    cache = {name: ops.store_token(buf, layer, position, new[name])
             for name, buf in cache.items()}
    if int8_cache:                  # dequantized: a stack of one layer
        k_stack = dequantize_kv(cache["k"][layer],
                                cache["k_scale"][layer])[None]
        v_stack = dequantize_kv(cache["v"][layer],
                                cache["v_scale"][layer])[None]
        at = 0
    else:
        k_stack, v_stack, at = cache["k"], cache["v"], layer
    out = _maybe_pallas_decode(cfg, q, k_stack, v_stack, position + 1, at)
    if out is None:
        kv_pos = jnp.arange(s_max)[None, None, :]          # [1,1,S]
        mask = kv_pos <= position[:, None, None]           # [B,1,S]
        out = gqa_core(q, k_stack[at], v_stack[at], mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


# ---------------------------------------------------------------------------
# Cross attention (llama-3.2-vision style gated cross-attn layers)
# ---------------------------------------------------------------------------

def cross_attention(p: Dict, cfg: ModelConfig, x: jax.Array,
                    vis_kv: Dict) -> jax.Array:
    """x [B,S,d] attends over fixed vision K/V [B,Tv,KV,hd]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if q.shape[1] > _BLOCKED_THRESHOLD:
        out = gqa_blocked(q, vis_kv["k"], vis_kv["v"], causal=False,
                          unroll=cfg.scan_unroll)
    else:
        out = gqa_core(q, vis_kv["k"], vis_kv["v"], None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def vision_kv(p: Dict, cfg: ModelConfig, vis: jax.Array) -> Dict:
    """Project (stub) vision embeddings [B,Tv,d] to cross-attn K/V once."""
    k = jnp.einsum("btd,dhk->bthk", vis, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", vis, p["wv"])
    return {"k": k, "v": v}
