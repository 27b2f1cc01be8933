"""Jitted public wrapper for the decode-attention kernel.

The kernel's custom call takes this wrapper's name, ``decode_attention``,
which is how a device trace tells a decode step's programs apart."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import (
    decode_attention_stacked_kernel)
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, kv_len, layer=0, block_k: int = None,
                     interpret: bool = None):
    """q [B,1,H,hd]; k/v either one layer's [B,S,KV,hd] or the stacked
    cache [L,B,S,KV,hd], read in place at ``layer`` (a scalar, traced
    inside the model's layer scan); kv_len [B] -> [B,1,H,hd]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if k.ndim == 4:
        k, v = k[None], v[None]
    return decode_attention_stacked_kernel(q, k, v, kv_len, layer,
                                           block_k=block_k,
                                           interpret=interpret)


reference = decode_attention_ref
