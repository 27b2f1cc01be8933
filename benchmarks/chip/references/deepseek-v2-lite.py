"""Plain float32 reference of DeepSeek-V2-Lite (deepseek-ai/DeepSeek-V2-Lite)
at one rank's share of a four-way expert-parallel deployment.

Written from the published architecture (arXiv:2405.04434 and the
model's config.json and modeling code), in straightforward jax.numpy at
``highest`` matmul precision, with no cache, no batching and no kernels:
token embedding; per layer RMSNorm, multi-head latent attention in its
decompressed form (query = x wq, no query LoRA; latent = RMSNorm of the
first kv_lora_rank of x wkv_a, the rest the shared rotary key; keys and
values = latent wkv_b), YaRN rotary embedding of the 64 rotary dims in
DeepSeek-V2's pairing (interleaved pairs permuted to rotate-half), the
softmax scale times YaRN's mscale(mscale_all_dim) squared, causal
attention, output projection, residual, RMSNorm, then layer 0's dense
SwiGLU or an expert layer, residual; a final RMSNorm and the untied
head.  The expert layer: a float32 softmax router over all routed
experts, the top-k by score (greedy), the weights not renormalised and
times routed_scaling_factor, each held expert's SwiGLU computed for every
token and combined by those weights, plus the shared experts (one SwiGLU
of their summed width).  Norm weights are 1, as the published model
initialises them.

Departure from the published model: the held share.  The chip holds
experts [experts_held_from, experts_held_from + n_routed_experts) of the
routed_experts_published the router scores; pairs routed to the others
add nothing, as in the program.  Nothing stands in for them.

It imports nothing of the program.  It makes its weights itself from the
seed with the recipe the served model's weights are drawn by (each
matrix a standard normal times 1/sqrt(fan_in), drawn in float32 from
``jax.random`` keys split in the same tree, rounded to the stored
dtype), so both sides hold the same numbers without the reference taking
anything the program made.  The weights stay in the stored dtype, whose
values they are, and are widened to float32 one layer at a time inside
the forward: the float32 copy of the whole share would not fit beside it
on one chip.  The control (``fp8_weights``) rounds every matrix through
float8_e4m3fn in the same place.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# configuration-file key -> the serving program's value
PROGRAM_KEYS = {
    "hidden_size": lambda c: c.d_model,
    "num_hidden_layers": lambda c: c.n_layers,
    "num_attention_heads": lambda c: c.n_heads,
    "num_key_value_heads": lambda c: c.n_kv_heads,
    "intermediate_size": lambda c: c.dense_first_d_ff,
    "moe_intermediate_size": lambda c: c.moe.d_expert,
    "vocab_size": lambda c: c.vocab_size,
    "rope_theta": lambda c: c.rope_theta,
    "rms_norm_eps": lambda c: c.norm_eps,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "hidden_act": lambda c: c.activation,
    "torch_dtype": lambda c: c.dtype,
    "q_lora_rank": lambda c: c.mla.q_lora_rank or None,
    "kv_lora_rank": lambda c: c.mla.kv_lora_rank,
    "qk_nope_head_dim": lambda c: c.mla.qk_nope_head_dim,
    "qk_rope_head_dim": lambda c: c.mla.qk_rope_head_dim,
    "v_head_dim": lambda c: c.mla.v_head_dim,
    "first_k_dense_replace": lambda c: int(c.dense_first_layer),
    "n_routed_experts": lambda c: c.moe.held,
    "routed_experts_published": lambda c: c.moe.n_experts,
    "experts_held_from": lambda c: c.moe.held_start,
    "num_experts_per_tok": lambda c: c.moe.top_k,
    "n_shared_experts": lambda c: c.moe.n_shared,
    "norm_topk_prob": lambda c: c.moe.norm_topk,
    "routed_scaling_factor": lambda c: c.moe.routed_scaling,
    "rope_scaling": lambda c: {
        "type": "yarn", "factor": c.yarn.factor,
        "original_max_position_embeddings": c.yarn.original_max_position,
        "beta_fast": c.yarn.beta_fast, "beta_slow": c.yarn.beta_slow,
        "mscale": c.yarn.mscale, "mscale_all_dim": c.yarn.mscale_all_dim},
    "use_pallas": lambda c: c.use_pallas,
}
# what this decoder is, in the program's terms
PROGRAM_FIXED = {
    "attention": lambda c: c.attention == "mla",
    "rope_interleaved": lambda c: c.mla.rope_interleaved,
    "gated_mlp": lambda c: c.gated_mlp,
    "layer_pattern": lambda c: c.layer_pattern == ("attn",),
    "moe_pattern": lambda c: c.moe_pattern == (True,),
    "no logit_softcap": lambda c: c.logit_softcap == 0.0,
    "no scale_embeddings": lambda c: not c.scale_embeddings,
}


def departures(config: dict, program_cfg) -> list:
    """Where the program's model config differs from the configuration
    file or from this decoder."""
    out = []
    for k, get in PROGRAM_KEYS.items():
        mine = get(program_cfg)
        want = config[k]
        if isinstance(want, dict):
            want = {n: want[n] for n in mine}
        if want != mine:
            out.append(f"{k}: file {want!r}, program {mine!r}")
    out += [f"{k}: not so in the program" for k, ok in PROGRAM_FIXED.items()
            if not ok(program_cfg)]
    return out


def config_of(program_cfg) -> dict:
    """The configuration-file keys of a program config (the CPU
    rehearsal's small preset)."""
    return {k: get(program_cfg) for k, get in PROGRAM_KEYS.items()}


def sizes(cfg: dict) -> tuple:
    """The hashable shape tuple the jitted functions are keyed by."""
    y = cfg["rope_scaling"]
    return (int(cfg["vocab_size"]), int(cfg["hidden_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
            int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
            int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"]),
            int(cfg["routed_experts_published"]),
            int(cfg["n_routed_experts"]), int(cfg["experts_held_from"]),
            int(cfg["num_experts_per_tok"]), int(cfg["n_shared_experts"]),
            float(cfg["routed_scaling_factor"]), float(cfg["rope_theta"]),
            float(y["factor"]), int(y["original_max_position_embeddings"]),
            float(y["beta_fast"]), float(y["beta_slow"]),
            float(y["mscale"]), float(y["mscale_all_dim"]),
            float(cfg["rms_norm_eps"]), str(cfg["torch_dtype"]))


def _dense(key, shape, dtype, fan_in):
    # the barrier keeps XLA from folding the scale into the normal's own
    # constant, which would round differently from the served weights
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (x * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _ffn(key, d, f, dtype):
    k = jax.random.split(key, 3)
    return {"w_up": _dense(k[0], (d, f), dtype, d),
            "w_down": _dense(k[1], (f, d), dtype, f),
            "w_gate": _dense(k[2], (d, f), dtype, d)}


def _attn(key, shp, dtype):
    _, d, _, h, r, nope, rope, vd = shp[:8]
    a = jax.random.split(key, 6)
    return {"wq": _dense(a[0], (d, h, nope + rope), dtype, d),
            "wkv_a": _dense(a[2], (d, r + rope), dtype, d),
            "wkv_b": _dense(a[3], (r, h, nope + vd), dtype, r),
            "wo": _dense(a[4], (h, vd, d), dtype, h * vd)}


# the stored weights leave this program in their own dtype, so that the
# rounding to it happens (inside one program XLA may keep a value in
# float32 that is cast down and back up); the expert layers are drawn
# one at a time, so that no layer's float32 draws exist beside another's
@functools.partial(jax.jit, static_argnums=(1,))
def _weights(key, shp):
    v, d, n_layers, _, _, _, _, _, f_dense, fe, n_pub, n_held = shp[:12]
    n_shared = shp[14]
    dtype = jnp.dtype(shp[-1])
    ks = jax.random.split(key, 7)
    k_a, k_f = ks[2], ks[3]

    def layer(k):
        k_attn, k_moe, _ = jax.random.split(k, 3)
        m = jax.random.split(k_moe, 5)
        return {"attn": _attn(k_attn, shp, dtype),
                "experts": {
                    "w_up": _dense(m[0], (n_held, d, fe), dtype, d),
                    "w_down": _dense(m[1], (n_held, fe, d), dtype, fe),
                    "w_gate": _dense(m[2], (n_held, d, fe), dtype, d)},
                "router": _dense(m[3], (d, n_pub), jnp.float32, d),
                "shared": _ffn(m[4], d, n_shared * fe, dtype)}

    return {"embed": _dense(ks[0], (v, d), dtype, d),
            "first": {"attn": _attn(k_a, shp, dtype),
                      "ffn": _ffn(k_f, d, f_dense, dtype)},
            "layers": jax.lax.map(layer, jax.random.split(ks[6],
                                                          n_layers - 1)),
            "head": _dense(ks[4], (d, v), dtype, d)}


def weights(seed: int, cfg: dict, device=None) -> dict:
    """The share's weights for ``seed``, made on ``device``, in the
    stored dtype (widened to float32 inside the forward)."""
    key = jax.random.PRNGKey(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _weights(key, sizes(cfg))


def fp8_weights(w: dict) -> dict:
    """The control's weights: every matrix through float8_e4m3fn, the
    precision one step below the configuration's bfloat16, with one
    scale per output channel.  The rounding is done inside the forward,
    on the same arrays (``logits``)."""
    return dict(w, control="fp8")


# input axes of each matrix, within one layer, that a control's scale
# spans (one scale per output channel)
_IN_AXES = {"wq": (0,), "wkv_a": (0,), "wkv_b": (0,), "wo": (0, 1),
            "w_up": (-2,), "w_down": (-2,), "w_gate": (-2,),
            "router": (0,), "embed": (1,), "head": (0,)}


def _widen(tree, fp8: bool):
    """float32 copies of one layer's matrices, through float8_e4m3fn for
    the control."""
    def one(path, x):
        x = x.astype(jnp.float32)
        if not fp8:
            return x
        axes = _IN_AXES[str(getattr(path[-1], "key", ""))]
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True)
                            / 448.0, 1e-30)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    return jax.tree_util.tree_map_with_path(one, tree)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_rope(x, shp):
    """x [S, heads, rope] at positions 0..S-1: DeepSeek-V2's YaRN rotary
    embedding (DeepseekV2YarnRotaryEmbedding, apply_rotary_pos_emb)."""
    theta, factor, orig, fast, slow, msc, msc_all = shp[16:23]
    s, _, dim = x.shape

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                dim - 1)
    if lo == hi:
        hi += 0.001
    freq_extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim)
    freq_inter = 1.0 / (factor * theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = freq_inter * (1.0 - keep) + freq_extra * keep
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    m = _mscale(factor, msc) / _mscale(factor, msc_all)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    # the interleaved pairs (2i, 2i+1) to rotate-half order
    x = x.reshape(s, -1, dim // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _attention(x, p, shp, causal):
    r, nope, rope = shp[4], shp[5], shp[6]
    eps = shp[23]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = x @ p["wkv_a"]
    ckv = _rms(kv_a[:, :r], eps)
    k_pe = kv_a[:, None, r:]
    kv = jnp.einsum("sr,rhk->shk", ckv, p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe, k_pe = _yarn_rope(q_pe, shp), _yarn_rope(k_pe, shp)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:2] + (rope,))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    scale = (nope + rope) ** -0.5 * _mscale(shp[17], shp[22]) ** 2
    sc = jnp.einsum("qhk,shk->hqs", q, k) * scale
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v)
    return jnp.einsum("qhk,hkd->qd", o, p["wo"])


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _experts(x, p, shp):
    """The held experts' part of the routed output plus the shared
    experts, for every token x [S, d]."""
    n_held, start, top_k = shp[11], shp[12], shp[13]
    scaling = shp[15]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)      # all experts
    w, idx = jax.lax.top_k(probs, top_k)
    w = w * scaling                                       # no renormalising
    held = jnp.arange(start, start + n_held)
    comb = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                             w[:, :, None], 0.0), axis=1)  # [S, held]
    e = p["experts"]
    h = (jax.nn.silu(jnp.einsum("sd,edf->sef", x, e["w_gate"]))
         * jnp.einsum("sd,edf->sef", x, e["w_up"]))
    y = jnp.einsum("sef,efd->sed", h, e["w_down"])
    return jnp.einsum("se,sed->sd", comb, y) + _swiglu(x, p["shared"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(w, tokens, shp, fp8):
    eps = shp[23]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    with jax.default_matmul_precision("highest"):
        outer = _widen({"embed": w["embed"], "head": w["head"]}, fp8)
        x = outer["embed"][tokens]
        first = _widen(w["first"], fp8)
        x = x + _attention(_rms(x, eps), first["attn"], shp, causal)
        x = x + _swiglu(_rms(x, eps), first["ffn"])

        def layer(x, p):
            p = _widen(p, fp8)
            x = x + _attention(_rms(x, eps), p["attn"], shp, causal)
            return x + _experts(_rms(x, eps), p, shp), None

        x, _ = jax.lax.scan(layer, x, w["layers"])
        return _rms(x, eps) @ outer["head"]


def logits(w: dict, cfg: dict, tokens, length: int) -> np.ndarray:
    """float32 logits [len(tokens), vocab] of the causal forward over
    ``tokens``, zero-padded to ``length`` so that every call shares one
    compile; causal attention keeps the padding out of every real
    position."""
    fp8 = w.get("control") == "fp8"
    w = {k: v for k, v in w.items() if k != "control"}
    n = len(tokens)
    toks = np.zeros((length,), np.int32)
    toks[:n] = tokens
    dev = next(iter(w["embed"].devices()))
    out = _forward(w, jax.device_put(toks, dev), sizes(cfg), fp8)
    return np.asarray(out[:n])
