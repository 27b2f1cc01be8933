"""Plain float32 reference of the Qwen3 dense decoder (Qwen/Qwen3-0.6B).

Written from the published architecture, in straightforward jax.numpy at
``highest`` matmul precision, with no cache, no batching and no kernels:
token embedding; per layer RMSNorm, q/k/v projections, RMSNorm of q and
k over the head dimension, rotary embedding (rotate-half, theta from the
config), causal grouped-query attention (query head i reads key/value
head i // (heads / kv_heads)), output projection, residual, RMSNorm,
SwiGLU MLP, residual; a final RMSNorm and the tied embedding as the
output head.  Norm weights are 1, as the published model initialises
them.

It imports nothing of the program.  It makes its weights itself from the
seed with the recipe the served model's weights are drawn by (each
matrix a standard normal times 1/sqrt(fan_in), drawn in float32 from
``jax.random`` keys split in the same tree, rounded to the stored dtype),
so both sides hold the same numbers without the reference taking
anything the program made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# configuration-file key -> the serving program's ModelConfig field
PROGRAM_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "activation", "torch_dtype": "dtype",
    "qk_norm": "qk_norm", "use_pallas": "use_pallas",
}
# what this decoder is, in the program's terms
PROGRAM_FIXED = {"attention": "gqa", "gated_mlp": True,
                 "layer_pattern": ("attn",), "moe": None,
                 "logit_softcap": 0.0, "scale_embeddings": False,
                 "dense_first_layer": False, "kv_cache_dtype": "bfloat16"}


def departures(config: dict, program_cfg) -> list:
    """Where the program's model config differs from the configuration
    file or from this decoder."""
    out = [f"{k}: file {config[k]!r}, program {getattr(program_cfg, a)!r}"
           for k, a in PROGRAM_KEYS.items()
           if config[k] != getattr(program_cfg, a)]
    out += [f"{a}: program {getattr(program_cfg, a)!r}, decoder {v!r}"
            for a, v in PROGRAM_FIXED.items()
            if getattr(program_cfg, a) != v]
    return out


def config_of(program_cfg) -> dict:
    """The configuration-file keys of a program config (the CPU
    rehearsal's small preset)."""
    return {k: getattr(program_cfg, a) for k, a in PROGRAM_KEYS.items()}


def sizes(cfg: dict) -> tuple:
    """The hashable shape tuple the jitted functions are keyed by."""
    return (int(cfg["vocab_size"]), int(cfg["hidden_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
            int(cfg["intermediate_size"]), float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]), str(cfg["torch_dtype"]))


def _dense(key, shape, dtype, fan_in):
    # the barrier keeps XLA from folding the scale into the normal's own
    # constant, which would round differently from the served weights
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (x * (1.0 / math.sqrt(fan_in))).astype(dtype)


# the stored weights leave this program in their own dtype, so that the
# rounding to it happens (inside one program XLA may keep a value in
# float32 that is cast down and back up)
@functools.partial(jax.jit, static_argnums=(1,))
def _weights(key, shp):
    v, d, n_layers, h, kv, hd, f, _, _, dtype = shp
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(key, 7)

    def layer(k):
        k_attn, k_ffn, _ = jax.random.split(k, 3)
        a = jax.random.split(k_attn, 6)
        m = jax.random.split(k_ffn, 3)
        return {"wq": _dense(a[0], (d, h, hd), dtype, d),
                "wk": _dense(a[1], (d, kv, hd), dtype, d),
                "wv": _dense(a[2], (d, kv, hd), dtype, d),
                "wo": _dense(a[3], (h, hd, d), dtype, h * hd),
                "w_up": _dense(m[0], (d, f), dtype, d),
                "w_down": _dense(m[1], (f, d), dtype, f),
                "w_gate": _dense(m[2], (d, f), dtype, d)}

    return {"embed": _dense(ks[0], (v, d), dtype, d),
            "layers": jax.vmap(layer)(jax.random.split(ks[6], n_layers))}


_to_f32 = jax.jit(lambda t: jax.tree.map(
    lambda x: x.astype(jnp.float32), t))


def weights(seed: int, cfg: dict, device=None) -> dict:
    """float32 weights for ``seed``, made on ``device``."""
    key = jax.random.PRNGKey(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _to_f32(_weights(key, sizes(cfg)))


# per matrix (layers stacked on axis 0), the input axes a scale spans
_IN_AXES = {"embed": (1,), "wq": (1,), "wk": (1,), "wv": (1,),
            "wo": (1, 2), "w_up": (1,), "w_down": (1,), "w_gate": (1,)}


@jax.jit
def _fp8(w: dict):
    """(float8_e4m3fn values, float32 scales): one scale per output
    channel, its largest |weight| mapped to the format's largest
    value."""
    def q(x, axes):
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True)
                            / 448.0, 1e-30)
        return (x / scale).astype(jnp.float8_e4m3fn), scale
    flat = {"embed": w["embed"], **w["layers"]}
    return {k: q(v, _IN_AXES[k]) for k, v in flat.items()}


def fp8_weights(w: dict) -> dict:
    """The control's weights: every matrix through float8_e4m3fn, the
    precision one step below the configuration's bfloat16."""
    deq = _to_f32(_fp8(w))
    out = {k: v * s for k, (v, s) in deq.items()}
    return {"embed": out.pop("embed"), "layers": out}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1, rotate-half convention."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _forward(w, tokens, shp):
    _, d, _, h, kv, hd, _, theta, eps, _ = shp
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = _rms(x, eps)
        q = _rms(jnp.einsum("sd,dhk->shk", a, p["wq"]), eps)
        k = _rms(jnp.einsum("sd,dhk->shk", a, p["wk"]), eps)
        v = jnp.einsum("sd,dhk->shk", a, p["wv"])
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v)
        x = x + jnp.einsum("qhk,hkd->qd", o, p["wo"])
        m = _rms(x, eps)
        g = jax.nn.silu(m @ p["w_gate"]) * (m @ p["w_up"])
        return x + g @ p["w_down"], None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, w["embed"][tokens], w["layers"])
        return _rms(x, eps) @ w["embed"].T


def logits(w: dict, cfg: dict, tokens, length: int) -> np.ndarray:
    """float32 logits [len(tokens), vocab] of the causal forward over
    ``tokens``, zero-padded to ``length`` so that every call shares one
    compile; causal attention keeps the padding out of every real
    position."""
    n = len(tokens)
    toks = np.zeros((length,), np.int32)
    toks[:n] = tokens
    dev = next(iter(w["embed"].devices()))
    out = _forward(w, jax.device_put(toks, dev), sizes(cfg))
    return np.asarray(out[:n])
