"""Compile the serving path's Pallas kernels and decode step for a
described TPU v5e.

Nothing runs: the TPU compiler, which is installed with jax, compiles for
a chip that is described and not attached.  A kernel that interpret mode
accepts can still be refused here (block shapes not aligned to the
tiling, too much fast memory), so these tests guard what the CPU kernel
tests cannot.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import (
    decode_attention_kernel, decode_attention_stacked_kernel)
from repro.kernels.flash_attention import ops as fa
from repro.models import model as model_lib
from repro.models import params as params_lib
from repro.serving import engine

QWEN3 = get_config("qwen3-0.6b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_decode_kernel_compiles_for_v5e(one_chip):
    b, s, h, kv, hd = 8, 2048, QWEN3.n_heads, QWEN3.n_kv_heads, 128
    bf16 = jnp.bfloat16
    hlo = _hlo(functools.partial(decode_attention_kernel, interpret=False),
               one_chip, ((b, 1, h, hd), bf16), ((b, s, kv, hd), bf16),
               ((b, s, kv, hd), bf16), ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_stacked_decode_kernel_compiles_for_v5e(one_chip):
    """The kernel on the whole layer-stacked cache at qwen3-0.6b's widths
    (28 layers, 16 slots x 2048), the layer a traced scalar: its K/V tiles
    of every KV head fit the chip's fast memory."""
    n_layers, b, s = QWEN3.n_layers, 16, 2048
    h, kv, hd = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.head_dim
    bf16 = jnp.bfloat16
    hlo = _hlo(functools.partial(decode_attention_stacked_kernel,
                                 interpret=False),
               one_chip, ((b, 1, h, hd), bf16),
               ((n_layers, b, s, kv, hd), bf16),
               ((n_layers, b, s, kv, hd), bf16), ((b,), jnp.int32),
               ((), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq", [37, 128, 200, 512])
def test_flash_op_compiles_for_v5e(one_chip, sq):
    h, kv, hd = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.head_dim
    bf16 = jnp.bfloat16
    hlo = _hlo(functools.partial(fa.flash_attention, interpret=False),
               one_chip, ((1, sq, h, hd), bf16), ((1, sq, kv, hd), bf16),
               ((1, sq, kv, hd), bf16))
    assert "tpu_custom_call" in hlo


def test_decode_step_writes_the_cache_in_place(one_chip, monkeypatch):
    """The engine's decode step at published widths (16 slots x 2048, the
    cache donated) stores each token's K/V into the stacked cache in
    place and the decode-attention kernel reads them there: no copy of
    the whole cache, and no slice or head-major copy of one layer's K/V
    (its temporaries are smaller than one layer's K)."""
    # the decode op chooses interpret mode by the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(QWEN3, use_pallas=True)
    slots, cache_len = 16, 2048

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: params_lib.init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: model_lib.init_cache(cfg, slots, cache_len)))
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    decode = engine._build_fns(cfg, cache_len)[2]
    compiled = decode.lower(params, cache, toks).compile()
    mem = compiled.memory_analysis()
    kv_bytes = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache["layers"]))
    assert kv_bytes > 3.7e9                       # 3.76 GB of bf16 K/V
    layer_k = kv_bytes // 2 // QWEN3.n_layers
    assert layer_k == 67_108_864
    assert mem.temp_size_in_bytes < layer_k
    assert mem.alias_size_in_bytes >= 3.7e9
    hlo = compiled.as_text()
    kernels = re.findall(r"(%\S+) = \S+ custom-call\(.*tpu_custom_call",
                         hlo)
    assert kernels and all(n.startswith("%decode_attention.")
                           for n in kernels), kernels
    stacked = "bf16[%s]" % ",".join(map(str, cache["layers"][0]["k"].shape))
    assert stacked == "bf16[28,16,2048,8,128]"
    copies = re.findall(r"(%\S+) = " + re.escape(stacked)
                        + r"\{[^}]*\} copy\(", hlo)
    assert not copies, copies
    for layer in ("bf16[16,2048,8,128]", "bf16[1,16,2048,8,128]",
                  "bf16[16,8,2048,128]"):
        made = re.findall(r"(%\S+) = " + re.escape(layer)
                          + r"\{[^}]*\} (?:copy|fusion|dynamic-slice)\(",
                          hlo)
        assert not made, (layer, made)


DSV2 = get_config("deepseek-v2-lite")


def _described_fns(cfg, one_chip, slots, cache_len):
    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: params_lib.init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: model_lib.init_cache(cfg, slots, cache_len)))
    return params, cache, engine._build_fns(cfg, cache_len)


def test_dsv2_decode_step_writes_the_latent_cache_in_place(one_chip):
    """deepseek-v2-lite's decode step at published widths, one chip's
    expert share (32 slots x 2048, the cache donated): it stores each
    token's latent and rotary key into the stacked cache in place, with
    no copy of the whole cache, and hands back the routing counts."""
    cfg = dataclasses.replace(DSV2, use_pallas=True)
    slots, cache_len = 32, 2048
    params, cache, (_, _, decode) = _described_fns(cfg, one_chip, slots,
                                                   cache_len)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    lowered = decode.lower(params, cache, toks)
    counts = lowered.out_info[2]
    assert counts.shape == (26, 16) and counts.dtype == jnp.int32
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    kv_bytes = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache))
    assert 9.8e9 < weight_bytes < 9.9e9           # 4.91 B, bf16
    assert 2.03e9 < kv_bytes < 2.05e9             # 27 x 576 x 2 B x 64k
    assert mem.alias_size_in_bytes >= kv_bytes - 1e6
    assert mem.temp_size_in_bytes < 0.1e9
    hlo = compiled.as_text()
    for name in ("ckv", "kr"):
        stacked = "bf16[%s]" % ",".join(
            map(str, cache["layers"][0][name].shape))
        copies = re.findall(r"(%\S+) = " + re.escape(stacked)
                            + r"\{[^}]*\} copy\(", hlo)
        assert not copies, (name, copies)
    assert "bf16[26,32,2048,512]" in hlo


def test_dsv2_prefill_compiles_for_v5e(one_chip):
    """A 1024-token prefill, the longest prompt of the cell's grid."""
    cfg = dataclasses.replace(DSV2, use_pallas=True)
    params, _, (prefill, _, _) = _described_fns(cfg, one_chip, 1, 2048)
    toks = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    mem = prefill.lower(params, toks).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
