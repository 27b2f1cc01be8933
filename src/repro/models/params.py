"""Parameter initialization + analytic counting for the model zoo.

Params are nested dicts of jnp arrays.  Layers that repeat are stacked with a
leading ``n_periods`` dimension (one stacked tree per position in the layer
period) so the forward pass can ``lax.scan`` over them.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _dense(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    # the barrier keeps a compiled init from folding the scale into the
    # normal's own constants: the same numbers whether drawn op by op or
    # inside one program (init_params maps the layers)
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (x * scale).astype(dtype)


def _expert_storage(cfg: ModelConfig, data_shards: int) -> int:
    """Physical leading dim of routed-expert weights.

    For the expert-parallel path each of the ``data_shards`` devices owns one
    slot; experts are replicated ``R = shards // E`` times when E < shards
    (grad symmetrization handles training).  For non-EP impls it is the
    experts held here (all E unless the config holds a share).
    """
    e = cfg.moe.held
    if cfg.moe.impl == "ep" and data_shards > 0:
        if e < data_shards:
            assert data_shards % e == 0, (e, data_shards)
            return data_shards
        assert e % data_shards == 0, (e, data_shards)
    return e


def init_ffn(key, cfg: ModelConfig, d_ff: int, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p = {"w_up": _dense(ks[0], (d, d_ff), dtype),
         "w_down": _dense(ks[1], (d_ff, d), dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = _dense(ks[2], (d, d_ff), dtype)
    return p


def init_moe(key, cfg: ModelConfig, dtype, data_shards: int) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    d, m = cfg.d_model, cfg.moe
    e_store = _expert_storage(cfg, data_shards)
    fe = m.d_expert
    # routed experts: stacked [E_store, ...]
    routed = {"w_up": _dense(ks[0], (e_store, d, fe), dtype, fan_in=d),
              "w_down": _dense(ks[1], (e_store, fe, d), dtype, fan_in=fe)}
    if cfg.gated_mlp:
        routed["w_gate"] = _dense(ks[2], (e_store, d, fe), dtype, fan_in=d)
    p = {"router": _dense(ks[3], (d, m.n_experts), jnp.float32),
         "routed": routed}
    if m.n_shared > 0:
        p["shared"] = init_ffn(ks[4], cfg, m.n_shared * fe, dtype)
    return p


def init_attn(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {"wq": _dense(ks[0], (d, h, hd), dtype, fan_in=d),
         "wk": _dense(ks[1], (d, kv, hd), dtype, fan_in=d),
         "wv": _dense(ks[2], (d, kv, hd), dtype, fan_in=d),
         "wo": _dense(ks[3], (h, hd, d), dtype, fan_in=h * hd)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_mla(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        p = {"wq_a": _dense(ks[0], (d, m.q_lora_rank), dtype),
             "q_norm_a": jnp.zeros((m.q_lora_rank,), dtype),
             "wq_b": _dense(ks[1], (m.q_lora_rank, h, qk), dtype,
                            fan_in=m.q_lora_rank)}
    else:
        p = {"wq": _dense(ks[0], (d, h, qk), dtype, fan_in=d)}
    p.update({
        "wkv_a": _dense(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                        dtype),
        "kv_norm_a": jnp.zeros((m.kv_lora_rank,), dtype),
        "wkv_b": _dense(ks[3], (m.kv_lora_rank, h,
                                m.qk_nope_head_dim + m.v_head_dim), dtype,
                        fan_in=m.kv_lora_rank),
        "wo": _dense(ks[4], (h, m.v_head_dim, d), dtype,
                     fan_in=h * m.v_head_dim),
    })
    return p


def init_mamba(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    d, m, dtr = cfg.d_model, cfg.mamba, cfg.dt_rank
    di, ds, dc = m.d_inner, m.d_state, m.d_conv
    with jax.ensure_compile_time_eval():    # the same constant however
        a_log = jnp.log(jnp.tile(             # the layers are mapped
            jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1)))
    return {
        "in_proj": _dense(ks[0], (d, 2 * di), dtype),
        "conv_w": _dense(ks[1], (dc, di), dtype, fan_in=dc),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _dense(ks[2], (di, dtr + 2 * ds), dtype, fan_in=di),
        "dt_proj": _dense(ks[3], (dtr, di), dtype, fan_in=dtr),
        "dt_bias": jnp.full((di,), -4.6, dtype),   # softplus^-1(0.01)
        "A_log": a_log,
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(ks[4], (di, d), dtype, fan_in=di),
    }


def init_layer(key, cfg: ModelConfig, pos_in_period: int, dtype,
               data_shards: int) -> Dict[str, Any]:
    kind = cfg.layer_pattern[pos_in_period]
    is_moe = cfg.moe_pattern[pos_in_period]
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if kind == "mamba":
        p["mamba"] = init_mamba(ks[0], cfg, dtype)
    elif kind == "cross":
        p["attn"] = init_attn(ks[0], cfg, dtype)
        p["gate_attn"] = jnp.zeros((), dtype)
        p["gate_ffn"] = jnp.zeros((), dtype)
    elif cfg.attention == "mla":
        p["attn"] = init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = init_attn(ks[0], cfg, dtype)
    # feed-forward sub-block (absent for pure-SSM archs with d_ff == 0)
    has_ffn = is_moe or cfg.d_ff > 0
    if kind == "mamba" and cfg.d_ff == 0 and not is_moe:
        has_ffn = False
    if has_ffn:
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        if is_moe:
            p["moe"] = init_moe(ks[1], cfg, dtype, data_shards)
        else:
            p["ffn"] = init_ffn(ks[1], cfg, cfg.d_ff, dtype)
    return p


def init_params(key, cfg: ModelConfig, data_shards: int = 0) -> Dict[str, Any]:
    """Initialize the full parameter tree.

    data_shards: size of the expert-parallel axis (only used when the MoE
    impl is "ep" to size physical expert storage).
    """
    dtype = _dtype(cfg)
    n_keys = 6 + cfg.period
    ks = jax.random.split(key, n_keys)
    params: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = _dense(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                                 fan_in=cfg.d_model)
    if cfg.vision_tokens:
        params["vision_proj"] = _dense(ks[1], (cfg.vision_dim, cfg.d_model),
                                       dtype)
    if cfg.dense_first_layer:
        first = {"ln1": jnp.zeros((cfg.d_model,), dtype),
                 "attn": (init_mla(ks[2], cfg, dtype)
                          if cfg.attention == "mla"
                          else init_attn(ks[2], cfg, dtype)),
                 "ln2": jnp.zeros((cfg.d_model,), dtype),
                 "ffn": init_ffn(ks[3], cfg,
                                 cfg.dense_first_d_ff or cfg.d_ff, dtype)}
        params["first_layer"] = first
    # stacked per-period-position layer params, drawn one layer at a time
    # so that no layer's float32 draws exist beside the others'
    layers = []
    for p_idx in range(cfg.period):
        def one(k):
            return init_layer(k, cfg, p_idx, dtype, data_shards)
        layer_keys = jax.random.split(ks[6 + p_idx], cfg.n_periods)
        layers.append(jax.lax.map(one, layer_keys))
    params["layers"] = layers
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings or cfg.input_mode != "tokens":
        params["lm_head"] = _dense(ks[4], (cfg.d_model, cfg.vocab_size),
                                   dtype)
    return params


def abstract_params(cfg: ModelConfig, data_shards: int = 0):
    """ShapeDtypeStruct tree of the params (no allocation) for dry-runs."""
    return jax.eval_shape(
        lambda k: init_params(k, cfg, data_shards),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic parameter count.  active_only counts top-k routed experts
    (for MoE MODEL_FLOPS = 6 * N_active * D)."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    total = cfg.vocab_size * d if cfg.input_mode == "tokens" else 0
    if not cfg.tie_embeddings or cfg.input_mode != "tokens":
        total += d * cfg.vocab_size
    if cfg.vision_tokens:
        total += cfg.vision_dim * d

    def ffn_count(f):
        return d * f * (3 if cfg.gated_mlp else 2)

    def attn_count():
        if cfg.attention == "mla":
            m = cfg.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            q = (d * m.q_lora_rank + m.q_lora_rank * h * qk
                 if m.q_lora_rank else d * h * qk)
            return (q + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    + m.kv_lora_rank * h * (m.qk_nope_head_dim
                                            + m.v_head_dim)
                    + h * m.v_head_dim * d
                    + m.q_lora_rank + m.kv_lora_rank)
        base = d * h * hd + 2 * d * kv * hd + h * hd * d
        if cfg.qk_norm:
            base += 2 * hd
        return base

    def mamba_count():
        m, dtr = cfg.mamba, cfg.dt_rank
        di, ds, dc = m.d_inner, m.d_state, m.d_conv
        return (d * 2 * di + dc * di + di + di * (dtr + 2 * ds)
                + dtr * di + di + di * ds + di + di * d)

    def moe_count():
        m = cfg.moe
        per = m.d_expert * d * (3 if cfg.gated_mlp else 2)
        n_routed = m.top_k if active_only else m.held
        c = d * m.n_experts + n_routed * per
        if m.n_shared:
            c += ffn_count(m.n_shared * m.d_expert)
        return c

    total += d  # final_norm
    for i in range(cfg.n_scan_layers):
        kind = cfg.layer_kind(i)
        total += d  # ln1
        if kind == "mamba":
            total += mamba_count()
        else:
            total += attn_count()
        if kind == "cross":
            total += 2  # gates
        if cfg.layer_is_moe(i):
            total += d + moe_count()
        elif not (kind == "mamba" and cfg.d_ff == 0):
            total += d + ffn_count(cfg.d_ff)
    if cfg.dense_first_layer:
        total += 2 * d + attn_count() + ffn_count(
            cfg.dense_first_d_ff or cfg.d_ff)
    return total
