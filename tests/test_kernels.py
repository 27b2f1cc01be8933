"""Pallas kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles (interpret=True executes the kernel body on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as da
from repro.kernels.flash_attention import ops as fa
from repro.kernels.mamba_scan import ops as ms


@pytest.mark.parametrize("shape", [
    # (B, S, H, KV, hd, bq, bk)
    (1, 128, 4, 4, 32, 64, 64),
    (2, 256, 8, 2, 64, 128, 64),
    (1, 512, 4, 1, 128, 128, 128),     # MQA
    (2, 128, 6, 2, 64, 128, 128),      # blocks > S get clamped
    (1, 37, 4, 2, 32, 128, 128),       # one block shorter than 128
    (1, 200, 4, 2, 32, 128, 128),      # padded up to a block multiple
    (2, 300, 4, 4, 32, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(shape, dtype):
    b, s, h, kv, hd, bq, bk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                             interpret=True)
    ref = fa.reference(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [
    # (B, S, H, KV, hd, bk)
    (2, 256, 8, 2, 64, 64),
    (1, 512, 4, 4, 32, 128),
    (3, 128, 16, 2, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(shape, dtype):
    b, s, h, kv, hd, bk = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    lens = jnp.asarray(np.random.default_rng(0).integers(1, s + 1, b),
                       jnp.int32)
    out = da.decode_attention(q, k, v, lens, block_k=bk, interpret=True)
    ref = da.reference(q, k, v, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


STACKED = [
    # (L, S, H, KV, hd, bk): rows get lengths 1, bk, bk + 1 and S
    (1, 256, 8, 4, 64, 64),            # g 2, one layer
    (3, 256, 8, 4, 64, 64),            # g 2
    (3, 512, 16, 2, 64, 128),          # g 8
    (3, 128, 8, 8, 32, 32),            # KV = H: the TP-expanded layout
    (3, 256, 4, 1, 128, None),         # MQA, the tile from the shapes
]


def _stacked_case(shape, dtype):
    n_layers, s, h, kv, hd, bk = shape
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (4, 1, h, hd), dtype)
    k = jax.random.normal(ks[1], (n_layers, 4, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (n_layers, 4, s, kv, hd), dtype)
    step = bk or s
    lens = jnp.asarray([1, step, min(step + 1, s), s], jnp.int32)
    return q, k, v, lens, bk


def _assert_matches_ref(out, q, k, v, lens, dtype):
    ref = da.reference(q, k, v, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", STACKED)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_stacked_in_a_layer_scan(shape, dtype):
    """Inside a layer scan, as the model calls it, the layer index is a
    traced value: each layer's output is the oracle's on that layer."""
    q, k, v, lens, bk = _stacked_case(shape, dtype)

    def body(_, layer):
        return None, da.decode_attention(q, k, v, lens, layer, block_k=bk,
                                         interpret=True)

    _, outs = jax.jit(lambda: jax.lax.scan(body, None,
                                           jnp.arange(k.shape[0])))()
    for layer in range(k.shape[0]):
        _assert_matches_ref(outs[layer], q, k[layer], v[layer], lens, dtype)


@pytest.mark.parametrize("shape", STACKED)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_stacked_reads_one_layer(shape, dtype):
    """The other layers are never read: NaNs written into them leave
    the selected layer's output as the oracle gives it."""
    q, k, v, lens, bk = _stacked_case(shape, dtype)
    layer = k.shape[0] // 2
    others = jnp.arange(k.shape[0]) != layer
    poison = lambda x: jnp.where(others[:, None, None, None, None],
                                 jnp.nan, x).astype(dtype)
    out = jax.jit(functools.partial(da.decode_attention, block_k=bk,
                                    interpret=True))(
        q, poison(k), poison(v), lens, jnp.int32(layer))
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    _assert_matches_ref(out, q, k[layer], v[layer], lens, dtype)


@pytest.mark.parametrize("shape", [
    # (B, S, di, ds, chunk, bc)
    (1, 64, 128, 8, 16, 64),
    (2, 32, 256, 16, 32, 128),
    (1, 128, 128, 4, 64, 128),
])
def test_mamba_scan(shape):
    b, s, di, ds, chunk, bc = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    xc = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di))) * 0.1
    bm = jax.random.normal(ks[2], (b, s, ds))
    cm = jax.random.normal(ks[3], (b, s, ds))
    al = jnp.log(jnp.abs(jax.random.normal(ks[4], (di, ds))) + 0.5)
    d = jnp.ones((di,))
    y, hf = ms.mamba_scan(xc, dt, bm, cm, al, d, chunk=chunk, block_c=bc,
                          interpret=True)
    yr, hr = ms.reference(xc, dt, bm, cm, al, d,
                          jnp.zeros((b, di, ds)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=1e-4,
                               atol=1e-4)


def test_mamba_scan_matches_model_path():
    """The kernel agrees with the model's chunked associative scan."""
    from repro.models.mamba import selective_scan
    b, s, di, ds = 2, 64, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    xc = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di))) * 0.1
    bm = jax.random.normal(ks[2], (b, s, ds))
    cm = jax.random.normal(ks[3], (b, s, ds))
    al = jnp.log(jnp.abs(jax.random.normal(ks[4], (di, ds))) + 0.5)
    d = jnp.ones((di,))
    h0 = jnp.zeros((b, di, ds))
    y1, h1 = ms.mamba_scan(xc, dt, bm, cm, al, d, chunk=16, block_c=64,
                           interpret=True)
    y2, h2 = selective_scan(xc, dt, bm, cm, al, d, h0, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)


def test_decode_attention_int8_cache():
    """int8-quantized KV cache: in-kernel dequant matches the dequantized
    oracle tightly and the exact oracle within quantization noise."""
    from repro.kernels.decode_attention.kernel import decode_attention_kernel
    from repro.models.attention import dequantize_kv, quantize_kv
    b, s, h, kv, hd = 2, 256, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    lens = jnp.array([100, 256])
    kq, kscale = quantize_kv(k)
    vq, vscale = quantize_kv(v)
    out = decode_attention_kernel(q.astype(jnp.bfloat16), kq, vq, lens,
                                  block_k=64, k_scale=kscale,
                                  v_scale=vscale, interpret=True)
    ref = da.reference(q, dequantize_kv(kq, kscale).astype(jnp.float32),
                       dequantize_kv(vq, vscale).astype(jnp.float32), lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)
    exact = da.reference(q, k, v, lens)
    assert float(jnp.abs(out.astype(jnp.float32) - exact).max()) < 0.05


def test_int8_kv_cache_decode_matches_bf16():
    """Model-level int8 cache path stays within 5% relative logit error."""
    import dataclasses
    from repro.configs import REGISTRY
    from repro.models import model as M
    from repro.models import params as P
    cfg = dataclasses.replace(REGISTRY["gemma-7b"].reduced(),
                              kv_cache_dtype="int8")
    pr = P.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    full, _ = M.forward_train(pr, cfg, tokens=toks)
    last, cache = M.prefill(pr, cfg, tokens=toks[:, :12], cache_len=16)
    errs = [float(jnp.abs(last - full[:, 11]).max())]
    for t in range(4):
        lg, cache = M.decode_step(pr, cfg, cache, tokens=toks[:, 12 + t])
        errs.append(float(jnp.abs(lg - full[:, 12 + t]).max()))
    rel = max(errs) / float(jnp.abs(full).max())
    assert rel < 0.05
