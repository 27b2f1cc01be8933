"""Primitive neural ops shared across the model zoo (pure jnp)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dtype)


def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
    }[name]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor for a context ``factor``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies for rotary embeddings (half the head dim).

    With ``yarn`` (a ``YaRNConfig``) each frequency is blended between
    itself and itself over ``yarn.factor``: kept where it turns more than
    ``beta_fast`` times over ``original_max_position``, interpolated
    where it turns fewer than ``beta_slow`` times, linearly between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponent)
    if yarn is None:
        return freqs

    def dim_of(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None, interleaved: bool = False) -> jax.Array:
    """Rotary position embedding.

    x: [..., seq, n_heads, head_dim]; positions: [..., seq] (int32).
    ``yarn`` scales the frequencies and cos/sin (``rope_freqs``);
    ``interleaved`` takes the head dim as pairs (x[2i], x[2i+1]) and
    permutes them to rotate-half order first, as DeepSeek-V2 does.
    """
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, yarn)                 # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    # broadcast over heads: [..., S, 1, hd/2]
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    xf = x.astype(jnp.float32)
    if interleaved:
        xf = xf.reshape(*x.shape[:-1], head_dim // 2, 2).swapaxes(
            -1, -2).reshape(x.shape)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


def store_token(buf: jax.Array, layer, position: jax.Array,
                new: jax.Array) -> jax.Array:
    """Write one decoded token per row into a layer-stacked cache.

    buf [L, B, S, ...] holds every layer of the stack; new [B, 1, ...] is
    row b's entry for position[b] of ``layer``.  Only those B entries are
    written, in place.  A row whose position is past S writes nothing:
    free slots keep counting, so clamping would overwrite a live entry."""
    rows = jnp.arange(buf.shape[1])
    return buf.at[layer, rows, position].set(
        new[:, 0].astype(buf.dtype), mode="drop", unique_indices=True)


def causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """Boolean mask [q_len, kv_len]; True where attention is allowed.

    q_offset: absolute position of the first query (array or int).
    """
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross entropy.  logits [..., V], labels [...].

    The gold logit is picked with a one-hot contraction (NOT
    take_along_axis): gathering along a "model"-sharded vocab axis forces
    SPMD to replicate the full logits tensor."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_cross_entropy(hidden: jax.Array, w_head: jax.Array,
                          labels: jax.Array, softcap_val: float = 0.0,
                          block: int = 512,
                          unroll: bool = False) -> jax.Array:
    """Sequence-chunked CE: logits are materialized one [B, block, V]
    slab at a time (scanned), so the full [B, S, V] f32 logits tensor --
    tens of GB per device for 150k vocabularies -- never exists.

    hidden [B,S,d] (already final-normed); w_head [d,V]; labels [B,S].
    """
    b, s, d = hidden.shape
    block = min(block, s)
    assert s % block == 0
    n_blocks = s // block
    h = hidden.reshape(b, n_blocks, block, d).transpose(1, 0, 2, 3)
    y = labels.reshape(b, n_blocks, block).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, inp):
        h_blk, y_blk = inp
        logits = (h_blk @ w_head).astype(jnp.float32)
        logits = softcap(logits, softcap_val)
        logz = jax.nn.logsumexp(logits, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        gold = jnp.sum(jnp.where(iota == y_blk[..., None], logits, 0.0),
                       axis=-1)
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, y),
                            unroll=True if unroll else 1)
    return total / (b * s)
