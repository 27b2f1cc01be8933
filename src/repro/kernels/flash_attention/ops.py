"""Jitted public wrapper for the flash-attention kernel.

On CPU (no TPU backend) the kernel runs in interpret mode when explicitly
requested; the model's default CPU path is the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref


def _pad_seq(x, n):
    return jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0))) if n else x


@functools.partial(jax.jit, static_argnames=("causal", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = None):
    """Any sequence length: q and k/v are zero-padded up to a block
    multiple and the output is sliced back.  Padded keys sit after every
    real query, so the causal mask hides them."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sq, skv = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, sq), min(block_k, skv)
    pad_q, pad_k = -sq % block_q, -skv % block_k
    if pad_k and not (causal and q_offset + sq <= skv):
        raise ValueError("padding keys needs a causal mask with every "
                         "query at or before the last key")
    out = flash_attention_kernel(
        _pad_seq(q, pad_q), _pad_seq(k, pad_k), _pad_seq(v, pad_k),
        causal=causal, q_offset=q_offset, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return out[:, :sq]


reference = flash_attention_ref
