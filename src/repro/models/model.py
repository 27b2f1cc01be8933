"""Model assembly: scan-over-layers decoder supporting every assigned arch.

Three entry points:
  forward_train(params, cfg, tokens, ...)          -> (logits, aux)
  prefill(params, cfg, tokens, cache_len, ...)     -> (last_logits, cache)
  decode_step(params, cfg, cache, tokens, ...)     -> (logits, cache)

The layer stack is grouped into ``n_periods`` repetitions of a
``period``-long pattern (e.g. jamba: 7 mamba + 1 attn); parameters are
stacked with a leading n_periods axis and the stack is traversed with
``lax.scan`` so HLO size is independent of depth.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ops


def ffn_forward(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = ops.activation(cfg.activation)
    h = x @ p["w_up"]
    if cfg.gated_mlp:
        h = act(x @ p["w_gate"]) * h
    else:
        h = act(h)
    return h @ p["w_down"]


def cache_kv_heads(cfg: ModelConfig) -> int:
    """KV-head count as stored in the decode cache.  When KV doesn't
    divide the TP axis but H does, the cache stores EXPANDED heads (full
    H, "model"-sharded): per-device bytes shrink vs a replicated/hd-split
    layout and -- critically -- the per-step all-gather of the whole cache
    (q heads sharded vs cache hd sharded) disappears."""
    from repro.distributed import context as dist_ctx
    tp = dist_ctx.tp_size()
    if tp > 1 and cfg.n_kv_heads % tp != 0 and cfg.n_heads % tp == 0:
        return cfg.n_heads
    return cfg.n_kv_heads


def _empty_cache_entry(cfg: ModelConfig, kind: str, batch: int,
                       cache_len: int, dtype):
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    if kind not in ("mamba",) and cfg.attention != "mla":
        kv = cache_kv_heads(cfg)
    if kind == "mamba":
        m = cfg.mamba
        return {"conv": jnp.zeros((batch, m.d_conv - 1, m.d_inner), dtype),
                "ssm": jnp.zeros((batch, m.d_inner, m.d_state), dtype)}
    if kind == "cross":
        return {"k": jnp.zeros((batch, cfg.vision_tokens, kv, hd), dtype),
                "v": jnp.zeros((batch, cfg.vision_tokens, kv, hd), dtype)}
    if cfg.attention == "mla":
        m = cfg.mla
        return {"ckv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, cache_len, m.qk_rope_head_dim),
                                dtype)}
    if cfg.kv_cache_dtype == "int8":
        return {"k": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
                "v": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
                "k_scale": jnp.ones((batch, cache_len, kv), jnp.bfloat16),
                "v_scale": jnp.ones((batch, cache_len, kv), jnp.bfloat16)}
    return {"k": jnp.zeros((batch, cache_len, kv, hd), dtype),
            "v": jnp.zeros((batch, cache_len, kv, hd), dtype)}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    """Zero-filled decode cache (also the donation target for serve_step)."""
    dtype = jnp.dtype(cfg.dtype)
    layers = []
    for pos in range(cfg.period):
        kind = cfg.layer_kind(pos)
        entry = _empty_cache_entry(cfg, kind, batch, cache_len, dtype)
        layers.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape),
            entry))
    cache: Dict[str, Any] = {"layers": layers,
                             "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.dense_first_layer:
        cache["first_layer"] = _empty_cache_entry(
            cfg, "attn", batch, cache_len, dtype)
    return cache


# ---------------------------------------------------------------------------
# single-layer forward
# ---------------------------------------------------------------------------

def _layer(p: Dict, cfg: ModelConfig, pos_in_period: int, x: jax.Array,
           positions: jax.Array, mode: str, cache_entry, vis: Optional[
               jax.Array], cache_len: int, layer=None):
    """One layer.  Returns (x, new_cache_entry, aux, counts).

    In decode mode ``cache_entry`` holds every layer of this kind stacked
    on a leading axis and ``layer`` indexes this one: the layer writes only
    what it changes into the stack and returns the stack.  ``counts`` is
    the tokens routed to each held expert in a decoded MoE layer
    (``moe.held_counts``), else None."""
    kind = cfg.layer_kind(pos_in_period)
    h = ops.rmsnorm(x, p["ln1"], cfg.norm_eps)
    new_entry = cache_entry
    if kind == "mamba":
        if mode == "decode":
            y, state = mamba_mod.mamba_decode(
                p["mamba"], cfg, h,
                jax.tree.map(lambda a: a[layer], cache_entry))
            new_entry = jax.tree.map(
                lambda a, s: a.at[layer].set(s.astype(a.dtype)),
                cache_entry, state)
        else:
            y, states = mamba_mod.mamba_seq(p["mamba"], cfg, h)
            if mode == "prefill":
                new_entry = states
    elif kind == "cross":
        if mode == "decode":                # read-only after prefill
            vis_kv = jax.tree.map(lambda a: a[layer], cache_entry)
        else:
            vis_kv = attn.vision_kv(p["attn"], cfg, vis)
            if mode == "prefill":
                new_entry = vis_kv
        y = attn.cross_attention(p["attn"], cfg, h, vis_kv)
        y = y * jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(y.dtype)
    elif cfg.attention == "mla":
        if mode == "train":
            y = mla_mod.mla_train(p["attn"], cfg, h, positions)
        elif mode == "prefill":
            y, new_entry = mla_mod.mla_prefill(p["attn"], cfg, h, positions,
                                               cache_len)
        else:
            y, new_entry = mla_mod.mla_decode(p["attn"], cfg, h, positions,
                                              cache_entry, layer)
    else:
        if mode == "train":
            y = attn.self_attention_train(p["attn"], cfg, h, positions)
        elif mode == "prefill":
            y, new_entry = attn.self_attention_prefill(p["attn"], cfg, h,
                                                       positions, cache_len)
        else:
            y, new_entry = attn.self_attention_decode(
                p["attn"], cfg, h, positions, cache_entry, layer)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    counts = None
    if "ln2" in p:
        h2 = ops.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y2, aux = moe_mod.moe_layer(p["moe"], cfg, h2)
            if mode == "decode":
                counts = moe_mod.held_counts(
                    p["moe"], cfg, h2.reshape(-1, h2.shape[-1]))
        else:
            y2 = ffn_forward(p["ffn"], cfg, h2)
        if kind == "cross":
            y2 = y2 * jnp.tanh(
                p["gate_ffn"].astype(jnp.float32)).astype(y2.dtype)
        x = x + y2
    return x, new_entry, aux, counts


# ---------------------------------------------------------------------------
# full stack
# ---------------------------------------------------------------------------

def _embed_inputs(params: Dict, cfg: ModelConfig, tokens, embeds):
    if cfg.input_mode == "tokens":
        x = params["embed"][tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    else:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    return x


def _head(params: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = ops.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return ops.softcap(logits, cfg.logit_softcap)


def _run_stack(params: Dict, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array, mode: str, cache: Optional[Dict],
               vis: Optional[jax.Array], cache_len: int):
    """Apply first_layer (if any) + the scanned periodic stack.

    Prefill stacks the layers' new cache entries as the scan's ``ys``.
    Decode carries the stacked cache through the scan instead, so each
    layer stores its one new token in place and the cache is never
    sliced out and stacked back whole; its ``ys`` are the scanned MoE
    layers' routing counts.  Returns (x, cache, aux, counts): counts
    int32 [MoE layers, held] in decode, else None."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = dict(cache) if cache is not None else None
    decode = mode == "decode"
    if cfg.dense_first_layer:
        entry = None
        if decode:                          # a stack of one layer
            entry = jax.tree.map(lambda a: a[None], cache["first_layer"])
        x, new_entry, aux, _ = _layer(params["first_layer"], cfg, 0, x,
                                      positions, mode, entry, vis,
                                      cache_len, layer=0)
        aux_total += aux
        if decode:
            new_cache["first_layer"] = jax.tree.map(lambda a: a[0],
                                                    new_entry)
        elif mode == "prefill":
            new_cache["first_layer"] = new_entry

    from repro.distributed import context as dist_ctx

    def body(carry, xs):
        h, aux_acc, entries = carry
        h = dist_ctx.constrain_batch(h)
        layer_params, i = xs
        new_entries, counts = [], []
        for pos in range(cfg.period):
            entry = None if entries is None else entries[pos]
            h, new_entry, aux, c = _layer(layer_params[pos], cfg, pos, h,
                                          positions, mode, entry, vis,
                                          cache_len, layer=i)
            new_entries.append(new_entry)
            if c is not None:
                counts.append(c)
        h = dist_ctx.constrain_batch(h)
        if decode:
            return (h, aux_acc + aux, new_entries), (
                jnp.stack(counts) if counts else None)
        return (h, aux_acc + aux, None), (
            new_entries if mode == "prefill" else None)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    carried = cache["layers"] if decode else None
    (x, aux_total, carried), stacked = jax.lax.scan(
        body, (x, aux_total, carried),
        (params["layers"], jnp.arange(cfg.n_periods)),
        unroll=True if cfg.scan_unroll else 1)
    counts = None
    if decode:
        new_cache["layers"] = carried
        if stacked is not None:
            counts = stacked.reshape(-1, stacked.shape[-1])
    elif mode == "prefill":
        new_cache["layers"] = stacked
    return x, new_cache, aux_total, counts


def forward_train(params: Dict, cfg: ModelConfig, tokens=None,
                  embeds=None, vision=None, positions=None):
    """Full-sequence forward (no cache).  Returns (logits [B,S,V], aux)."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if vision is not None and "vision_proj" in params:
        vis = vision.astype(x.dtype) @ params["vision_proj"]
    else:
        vis = None
    x, _, aux, _ = _run_stack(params, cfg, x, positions, "train", None, vis,
                              s)
    return _head(params, cfg, x), aux


def prefill(params: Dict, cfg: ModelConfig, tokens=None, embeds=None,
            vision=None, cache_len: int = 0, lengths=None):
    """Process the prompt, build the decode cache.

    Returns (last_token_logits [B,V], cache)."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[0], x.shape[1]
    cache_len = cache_len or cfg.max_seq_len
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if vision is not None and "vision_proj" in params:
        vis = vision.astype(x.dtype) @ params["vision_proj"]
    else:
        vis = None
    cache = init_cache(cfg, b, cache_len)
    x, cache, _, _ = _run_stack(params, cfg, x, positions, "prefill",
                                cache, vis, cache_len)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    cache["pos"] = lengths
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _head(params, cfg, last)[:, 0], cache


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict, tokens=None,
                embeds=None, routed: bool = False):
    """One decode step for the whole batch.  tokens [B] (or embeds [B,1,d]).

    Returns (logits [B,V], new_cache), and with ``routed`` (MoE configs)
    also the tokens routed to each held expert, int32 [MoE layers, held]
    (every row of the batch counts)."""
    if tokens is not None:
        x = _embed_inputs(params, cfg, tokens[:, None], None)
    else:
        x = _embed_inputs(params, cfg, None, embeds)
    positions = cache["pos"]                        # [B]
    x, cache, _, counts = _run_stack(params, cfg, x, positions, "decode",
                                     cache, None, 0)
    cache["pos"] = positions + 1
    out = (_head(params, cfg, x)[:, 0], cache)
    return out + (counts,) if routed else out


def forward_hidden(params: Dict, cfg: ModelConfig, tokens=None,
                   embeds=None, vision=None, positions=None):
    """Like forward_train but stops at the final-normed hidden states."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if vision is not None and "vision_proj" in params:
        vis = vision.astype(x.dtype) @ params["vision_proj"]
    else:
        vis = None
    x, _, aux, _ = _run_stack(params, cfg, x, positions, "train", None, vis,
                              s)
    return ops.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def loss_fn(params: Dict, cfg: ModelConfig, batch: Dict) -> Tuple[jax.Array,
                                                                  Dict]:
    """Next-token LM loss (+ MoE aux), with sequence-chunked CE so the
    full-vocab logits tensor is never materialized."""
    hidden, aux = forward_hidden(
        params, cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        vision=batch.get("vision"))
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        w_head = params["embed"].T
    else:
        w_head = params["lm_head"]
    loss = ops.chunked_cross_entropy(hidden, w_head, batch["labels"],
                                     cfg.logit_softcap,
                                     unroll=cfg.scan_unroll)
    total = loss
    if cfg.moe is not None:
        total = total + cfg.moe.router_aux_weight * aux
    return total, {"lm_loss": loss, "moe_aux": aux}
