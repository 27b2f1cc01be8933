"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Prefill/train use the standard (decompressed) path; decode uses the
*absorbed* path so per-step cost is O(S * (kv_lora + rope)) memory traffic —
the whole point of MLA's compressed KV cache.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import ops


def _rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    return ops.apply_rope(x, positions, cfg.rope_theta, cfg.yarn,
                          cfg.mla.rope_interleaved)


def _scale(cfg: ModelConfig):
    """Softmax scale: 1/sqrt(q.k width), times YaRN's temperature factor
    squared where the config scales its rope."""
    m = cfg.mla
    scale = 1.0 / jnp.sqrt(float(m.qk_nope_head_dim + m.qk_rope_head_dim))
    if cfg.yarn is not None:
        scale = scale * ops.yarn_mscale(cfg.yarn.factor,
                                        cfg.yarn.mscale_all_dim) ** 2
    return scale


def _project_q(p: Dict, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array):
    m = cfg.mla
    if m.q_lora_rank:
        q_lat = ops.rmsnorm(x @ p["wq_a"], p["q_norm_a"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = _rope(cfg, q[..., m.qk_nope_head_dim:], positions)
    return q_nope, q_rope


def _project_kv_latent(p: Dict, cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array):
    """Compressed latent ckv [B,S,r] and shared rotary key [B,S,rope]."""
    m = cfg.mla
    lat = x @ p["wkv_a"]
    ckv = ops.rmsnorm(lat[..., :m.kv_lora_rank], p["kv_norm_a"], cfg.norm_eps)
    k_rope = _rope(cfg, lat[..., None, m.kv_lora_rank:], positions)[..., 0, :]
    return ckv, k_rope


def mla_train(p: Dict, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    out, _ = mla_prefill(p, cfg, x, positions, cache_len=x.shape[1])
    return out


def mla_prefill(p: Dict, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, cache_len: int):
    """Standard decompressed attention; caches (ckv, k_rope)."""
    m = cfg.mla
    b, s, _ = x.shape
    q_nope, q_rope = _project_q(p, cfg, x, positions)
    ckv, k_rope = _project_kv_latent(p, cfg, x, positions)
    kv = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"])
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    scale = _scale(cfg)
    kn_f = k_nope.astype(jnp.float32)
    kr_f = k_rope.astype(jnp.float32)
    v_f = v.astype(jnp.float32)

    def attend(qn_blk, qr_blk, offset):
        sc = (jnp.einsum("bqhk,bshk->bhqs", qn_blk.astype(jnp.float32),
                         kn_f)
              + jnp.einsum("bqhk,bsk->bhqs", qr_blk.astype(jnp.float32),
                           kr_f)) * scale
        msk = ops.causal_mask(qn_blk.shape[1], s, offset)[None, None]
        sc = jnp.where(msk, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", w, v_f).astype(x.dtype)

    if s > 1024:
        # blocked over q so scores never exceed [B,H,bq,S] (32k cells)
        bq = 512
        n_blk = s // bq
        qn = q_nope.reshape(b, n_blk, bq, *q_nope.shape[2:]).transpose(
            1, 0, 2, 3, 4)
        qr = q_rope.reshape(b, n_blk, bq, *q_rope.shape[2:]).transpose(
            1, 0, 2, 3, 4)

        @jax.checkpoint
        def body(_, inp):
            qn_b, qr_b, i = inp
            from repro.distributed import context as dist_ctx
            return None, dist_ctx.constrain_batch(
                attend(qn_b, qr_b, i * bq))

        _, outs = jax.lax.scan(body, None, (qn, qr, jnp.arange(n_blk)),
                               unroll=True if cfg.scan_unroll else 1)
        o = outs.transpose(1, 0, 2, 3, 4).reshape(
            b, s, cfg.n_heads, m.v_head_dim)
    else:
        o = attend(q_nope, q_rope, 0)
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    pad = cache_len - s
    if pad > 0:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return out, {"ckv": ckv, "kr": k_rope}


def mla_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
               position: jax.Array, cache: Dict, layer):
    """Absorbed decode: score/value computed in the latent space.  cache =
    {ckv [L,B,S,r], kr [L,B,S,rope]} stacked over the layers, of which this
    is ``layer``; the new token's entries are stored into it in place."""
    m = cfg.mla
    pos = position[:, None]
    q_nope, q_rope = _project_q(p, cfg, x, pos)     # [B,1,H,*]
    ckv_new, kr_new = _project_kv_latent(p, cfg, x, pos)
    cache = {"ckv": ops.store_token(cache["ckv"], layer, position, ckv_new),
             "kr": ops.store_token(cache["kr"], layer, position, kr_new)}
    ckv_cache, kr_cache = cache["ckv"][layer], cache["kr"][layer]
    s_max = ckv_cache.shape[1]
    # absorb W_kv_b(k-part) into q:  q_lat [B,1,H,r]
    wkb_k = p["wkv_b"][..., :m.qk_nope_head_dim]    # [r,H,nope]
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, wkb_k)
    scale = _scale(cfg)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat.astype(jnp.float32),
                         ckv_cache.astype(jnp.float32))
              + jnp.einsum("bqhk,bsk->bhqs", q_rope.astype(jnp.float32),
                           kr_cache.astype(jnp.float32))) * scale
    kv_pos = jnp.arange(s_max)[None, None, None, :]
    mask = kv_pos <= position[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", w,
                       ckv_cache.astype(jnp.float32))  # [B,1,H,r]
    wkb_v = p["wkv_b"][..., m.qk_nope_head_dim:]       # [r,H,v]
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), wkb_v)
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    return out, cache
