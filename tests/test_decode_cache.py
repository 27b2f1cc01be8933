"""The decode step stores each row's new token into the layer-stacked
cache in place.  The reference here is the write it replaced: a one-hot
blend of the new token into its layer's whole cache, written back whole.
After a prefill, slot inserts and decode steps the cache is bit-identical
to the reference for every cache kind, and so are the logits."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import model as model_lib
from repro.models import ops
from repro.models import params as params_lib
from repro.serving import engine

SLOTS, CACHE_LEN, STEPS = 3, 16, 3
# (slot, prompt length): slot 2's third token falls at CACHE_LEN; slot 1 is
# a free slot whose position has already passed it
PROMPTS = ((0, 5), (2, 14))
FREE = 1

CASES = {
    "gqa-bf16": ("qwen3-0.6b", {"dtype": "bfloat16"}),
    "gqa-bf16-pallas": ("qwen3-0.6b", {"dtype": "bfloat16",
                                       "use_pallas": True}),
    "gqa-int8": ("qwen3-0.6b", {"dtype": "bfloat16",
                                "kv_cache_dtype": "int8"}),
    "mla": ("minicpm3-4b", {}),
    "mamba-attn": ("jamba-v0.1-52b", {}),
    "cross": ("llama-3.2-vision-11b", {}),
    "dense-first": ("deepseek-moe-16b", {}),
}


def onehot_store(buf, layer, position, new):
    """cache * (1 - onehot) + onehot * new over the layer's whole cache."""
    onehot_store.calls += 1
    cache = buf[layer]
    oh = jax.nn.one_hot(position, cache.shape[1], dtype=jnp.float32)
    oh = oh.reshape(oh.shape + (1,) * (cache.ndim - 2))
    blend = cache.astype(jnp.float32) * (1 - oh) \
        + oh * new.astype(jnp.float32)
    return buf.at[layer].set(blend.astype(buf.dtype))


def _serve(cfg, params):
    """Prefill each prompt alone, insert it into its slot, then decode
    STEPS tokens for all slots.  Returns the logits and the cache after
    the inserts and after each step."""
    vision = None
    if cfg.vision_tokens:
        vision = jax.random.normal(jax.random.PRNGKey(7),
                                   (1, cfg.vision_tokens, cfg.vision_dim))
    insert = engine._build_fns(cfg, CACHE_LEN)[1]
    cache = model_lib.init_cache(cfg, SLOTS, CACHE_LEN)
    for slot, n in PROMPTS:
        toks = jax.random.randint(jax.random.PRNGKey(slot), (1, n), 0,
                                  cfg.vocab_size)
        _, one = model_lib.prefill(params, cfg, tokens=toks, vision=vision,
                                   cache_len=CACHE_LEN)
        cache = insert(cache, one, slot)
    cache["pos"] = cache["pos"].at[FREE].set(CACHE_LEN)
    decode = jax.jit(lambda p, c, t: model_lib.decode_step(p, cfg, c,
                                                           tokens=t))
    caches, logits = [cache], []
    for t in range(STEPS):
        toks = jax.random.randint(jax.random.PRNGKey(100 + t), (SLOTS,), 0,
                                  cfg.vocab_size)
        lg, cache = decode(params, cache, toks)
        caches.append(cache)
        logits.append(lg)
    return logits, [jax.tree.map(np.asarray, c) for c in caches]


def _entries(cfg, cache):
    """(layer kind, entry, row axis) for every entry of the cache."""
    out = [(cfg.layer_kind(pos), e, 1)
           for pos, e in enumerate(cache["layers"])]
    if cfg.dense_first_layer:
        out.append(("attn", cache["first_layer"], 0))
    return out


def _take(entry, row, axis):
    return jax.tree.map(lambda a: np.take(a, row, axis=axis), entry)


def _assert_equal(a, b):
    ta, tb = jax.tree.structure(a), jax.tree.structure(b)
    assert ta == tb, (ta, tb)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.fixture(params=sorted(CASES), scope="module")
def served(request):
    arch, overrides = CASES[request.param]
    cfg = REGISTRY[arch].reduced(**overrides)
    params = params_lib.init_params(jax.random.PRNGKey(0), cfg)
    got = _serve(cfg, params)
    with pytest.MonkeyPatch.context() as mp:
        onehot_store.calls = 0
        mp.setattr(ops, "store_token", onehot_store)
        ref = _serve(cfg, params)
    return cfg, got, ref, onehot_store.calls


def test_decode_cache_matches_onehot_reference(served):
    cfg, (logits, caches), (ref_logits, ref_caches), calls = served
    assert calls > 0                      # the reference really wrote
    for lg, ref in zip(logits, ref_logits):
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref))
    for c, ref in zip(caches, ref_caches):
        _assert_equal(c, ref)


def test_rows_at_cache_len_write_nothing(served):
    """A row whose position has reached CACHE_LEN leaves its K/V as they
    were (mamba states advance regardless); cross-attention K/V are read
    and never written."""
    cfg, (_, caches), _, _ = served
    first, before_last, last = caches[0], caches[-2], caches[-1]
    slot_at_end = PROMPTS[-1][0]
    assert int(before_last["pos"][slot_at_end]) == CACHE_LEN
    pairs = zip(_entries(cfg, first), _entries(cfg, before_last),
                _entries(cfg, last))
    for (kind, e0, ax), (_, e2, _), (_, e3, _) in pairs:
        if kind == "mamba":
            continue
        if kind == "cross":
            _assert_equal(e3, e0)
            continue
        _assert_equal(_take(e3, FREE, ax), _take(e0, FREE, ax))
        _assert_equal(_take(e3, slot_at_end, ax),
                      _take(e2, slot_at_end, ax))
        # ...while a row inside the cache did get its token
        assert any(not np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(_take(e3, 0, ax)),
            jax.tree.leaves(_take(e2, 0, ax))))


def test_store_token_writes_one_entry_per_row():
    buf = jnp.zeros((3, 2, 4, 2), jnp.bfloat16)
    new = jnp.arange(1, 5, dtype=jnp.bfloat16).reshape(2, 1, 2)
    out = np.asarray(ops.store_token(buf, 1, jnp.array([2, 4]), new),
                     np.float32)
    want = np.zeros((3, 2, 4, 2), np.float32)
    want[1, 0, 2] = [1, 2]            # row 1's position 4 is past the end
    np.testing.assert_array_equal(out, want)


def test_pallas_decode_matches_jnp_decode():
    """The decode step through the Pallas kernel (interpret mode), which
    reads each layer's K/V in place out of the stacked cache, serves what
    the jnp path serves: from one prefilled cache, step after step, the
    same caches and the same logits to bf16 tolerance."""
    import dataclasses
    cfg = REGISTRY["qwen3-0.6b"].reduced(dtype="bfloat16", n_heads=8)
    assert cfg.n_heads == 2 * cfg.n_kv_heads and cfg.n_layers > 1
    params = params_lib.init_params(jax.random.PRNGKey(0), cfg)
    insert = engine._build_fns(cfg, CACHE_LEN)[1]
    cache = model_lib.init_cache(cfg, SLOTS, CACHE_LEN)
    for slot, n in PROMPTS:
        toks = jax.random.randint(jax.random.PRNGKey(slot), (1, n), 0,
                                  cfg.vocab_size)
        _, one = model_lib.prefill(params, cfg, tokens=toks,
                                   cache_len=CACHE_LEN)
        cache = insert(cache, one, slot)
    cache["pos"] = cache["pos"].at[FREE].set(CACHE_LEN)
    served = {}
    for use_pallas in (False, True):
        run = dataclasses.replace(cfg, use_pallas=use_pallas)
        decode = jax.jit(functools.partial(model_lib.decode_step, cfg=run))
        c, steps = cache, []
        for t in range(STEPS):
            toks = jax.random.randint(jax.random.PRNGKey(100 + t),
                                      (SLOTS,), 0, cfg.vocab_size)
            lg, c = decode(params, cache=c, tokens=toks)
            steps.append((np.asarray(lg, np.float32),
                          jax.tree.map(np.asarray, c)))
        served[use_pallas] = steps
    for (lg, c), (ref_lg, ref_c) in zip(served[True], served[False]):
        np.testing.assert_allclose(lg, ref_lg, rtol=2e-2, atol=2e-2)
        _assert_equal(c, ref_c)
