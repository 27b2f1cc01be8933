"""DeepSeek-V2-Lite at one rank's expert share, at its small preset on the
CPU, against the plain float32 reference the chip benchmark checks it
with (``benchmarks/chip/references/deepseek-v2-lite.py``): prefill and
decode through the engine's slot cache, the absorbed decode against the
decompressed attention, the router's unnormalised weights, the three
expert paths, the share of the layer each rank computes, and the
engine's routing counters.

The preset (``ModelConfig.reduced``) keeps every mechanism: a dense
layer 0 then two MoE layers, latent attention without query LoRA, YaRN
over 32 original positions on interleaved rotary pairs, top-3 of 8
experts by softmax without renormalising, 2 experts held from expert 0,
one shared expert; float32 throughout.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import V100_LLAMA2_7B
from repro.models import mla as mla_mod
from repro.models import model as model_lib
from repro.models import moe as moe_mod
from repro.models import params as params_lib
from repro.serving import engine
from repro.serving.engine import LLMInstance
from repro.serving.gateway import EngineClusterAdapter, Gateway, GatewayConfig
from repro.serving.policies import make_gateway_policy
from repro.serving.request import Request
from repro.serving.scheduler import FCFS
from repro.serving.trace import TraceRecorder

CFG = get_config("deepseek-v2-lite").reduced()
SEED = 2**31 + 15
CACHE_LEN = 64
# the engine's logits against the reference's, over the largest
# |reference logit|: float32 on both sides, differing in summation order
# and in the absorbed against the decompressed attention (read: 1.1e-6);
# a bfloat16 program, 8 bits of mantissa, reads 4.4e-2
LOGIT_TOL = 1e-4
# two float32 computations of one layer that differ only in the order of
# their sums (read: at most 3.3e-7 of the output's largest magnitude)
LAYER_TOL = 1e-5


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", "references",
        "deepseek-v2-lite.py")
    spec = importlib.util.spec_from_file_location("reference_dsv2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(scope="module")
def params():
    return params_lib.init_params(jax.random.PRNGKey(SEED), CFG)


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / np.abs(b).max())


def test_preset_keeps_every_mechanism():
    assert CFG.mla.q_lora_rank == 0 and CFG.mla.rope_interleaved
    assert CFG.yarn.original_max_position == 32 and CFG.yarn.factor == 40
    m = CFG.moe
    assert (m.n_experts, m.top_k, m.n_held, m.held_start) == (8, 3, 2, 0)
    assert not m.norm_topk and m.n_shared == 1
    assert CFG.dense_first_layer and CFG.n_scan_layers == 2
    assert ref.departures(ref.config_of(CFG), CFG) == []


def _engine_logits(cfg, params, prompt, steps):
    """Prefill ``prompt`` alone, insert it into slot 1 of a 3-slot cache
    (slot 0 holds another prompt), then decode ``steps`` tokens greedily.
    Returns the slot's sequence and its logits at each position from the
    prompt's last on."""
    prefill, insert, decode = engine._build_fns(cfg, CACHE_LEN)
    cache = model_lib.init_cache(cfg, 3, CACHE_LEN)
    other = np.arange(10, dtype=np.int32)[None] % cfg.vocab_size
    cache = insert(cache, prefill(params, other)[1], 0)
    logits, small = prefill(params, np.asarray(prompt, np.int32)[None])
    cache = insert(cache, small, 1)
    seq, rows = list(prompt), [logits[0]]
    for _ in range(steps):
        seq.append(int(jnp.argmax(rows[-1])))
        toks = np.array([1, seq[-1], 2], np.int32)
        lg, cache, counts = decode(params, cache, toks)
        assert counts.shape == (cfg.n_scan_layers, cfg.moe.held)
        rows.append(lg[1])
    return seq, np.stack([np.asarray(r, np.float32) for r in rows])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_agree_with_the_reference(dtype):
    """Positions run to 43, past the 32 original positions, so YaRN's
    blended frequencies matter.  A bfloat16 program is held to the same
    tolerance and has to miss it."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params = params_lib.init_params(jax.random.PRNGKey(SEED), cfg)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 24)
    seq, got = _engine_logits(cfg, params, prompt, 20)
    rcfg = ref.config_of(cfg)
    want = ref.logits(ref.weights(SEED, rcfg), rcfg, seq, CACHE_LEN)
    err = _rel(got, want[len(prompt) - 1:])
    if dtype == "float32":
        assert err <= LOGIT_TOL
    else:
        assert err > LOGIT_TOL


def test_absorbed_decode_equals_decompressed_attention(params):
    p = _layer(params["layers"][0]["attn"], 0)
    b, s = 2, 40
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.default_matmul_precision("highest"):
        full, _ = mla_mod.mla_prefill(p, CFG, x, pos, cache_len=s)
        _, prev = mla_mod.mla_prefill(p, CFG, x[:, :-1], pos[:, :-1],
                                      cache_len=s)
        stack = jax.tree.map(lambda a: a[None], prev)
        last, _ = mla_mod.mla_decode(p, CFG, x[:, -1:],
                                     jnp.full((b,), s - 1), stack, 0)
    assert _rel(last[:, 0], full[:, -1]) <= LAYER_TOL


def test_router_weights_are_not_renormalised(params):
    p = _layer(params["layers"][0]["moe"], 0)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, CFG.d_model))
    scaled = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, routed_scaling=2.5))
    probs, idx, w = moe_mod._router(p, x, scaled)
    want = jnp.take_along_axis(jax.nn.softmax(x @ p["router"]), idx, 1)
    np.testing.assert_allclose(w, 2.5 * want, rtol=1e-6)
    assert probs.shape == (16, CFG.moe.n_experts)
    assert float(jnp.max(jnp.sum(w, -1))) < 2.5
    normed = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, norm_topk=True))
    np.testing.assert_allclose(jnp.sum(moe_mod._router(p, x, normed)[2],
                                       -1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("t", [5, 24])
def test_expert_paths_agree_on_the_share(params, t):
    p = _layer(params["layers"][0]["moe"], 1)
    x = jax.random.normal(jax.random.PRNGKey(5 + t), (t, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        dense, _ = moe_mod.moe_dense(p, CFG, x)
        outs = [moe_mod.moe_ragged(p, CFG, x)[0],
                moe_mod.moe_gather(p, CFG, x)[0]]
    assert float(jnp.abs(dense).max()) > 0
    for y in outs:
        assert _rel(y, dense) <= LAYER_TOL


@pytest.mark.parametrize("impl", ["dense", "ragged", "gather"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(impl):
    """Four ranks of two experts each: their routed parts, with the shared
    expert counted once, give what the layer holding all 8 gives."""
    full = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, n_held=0, held_start=0,
                                     impl=impl))
    p = params_lib.init_moe(jax.random.PRNGKey(6), full, jnp.float32, 0)
    t = 6 if impl == "gather" else 20
    x = jax.random.normal(jax.random.PRNGKey(7), (1, t, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = moe_mod.moe_layer(p, full, x)
        total = model_lib.ffn_forward(p["shared"], full, x)
        for rank in range(4):
            cut = dataclasses.replace(full, moe=dataclasses.replace(
                full.moe, n_held=2, held_start=2 * rank, n_shared=0))
            share = {"router": p["router"], "routed": jax.tree.map(
                lambda a: a[2 * rank:2 * rank + 2], p["routed"])}
            part, _ = moe_mod.moe_layer(share, cut, x)
            assert float(jnp.abs(part).max()) > 0
            total = total + part
    assert _rel(total, want) <= LAYER_TOL


def _reference_routes(w, rcfg, tokens):
    """The reference's top-k experts of every MoE layer at every position
    of ``tokens``: int [MoE layers, positions, k]."""
    shp = ref.sizes(rcfg)
    eps, k = shp[23], shp[13]
    s = len(tokens)
    causal = jnp.tril(jnp.ones((s, s), bool))
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        first = ref._widen(w["first"], False)
        x = x + ref._attention(ref._rms(x, eps), first["attn"], shp, causal)
        x = x + ref._swiglu(ref._rms(x, eps), first["ffn"])
        routes = []
        for i in range(shp[2] - 1):
            p = ref._widen(_layer(w["layers"], i), False)
            x = x + ref._attention(ref._rms(x, eps), p["attn"], shp, causal)
            m = ref._rms(x, eps)
            routes.append(jax.lax.top_k(
                jax.nn.softmax(m @ p["router"], -1), k)[1])
            x = x + ref._experts(m, p, shp)
    return np.asarray(jnp.stack(routes))


def test_engine_routing_sums_match_the_reference_router(params):
    """Three full slots decode STEPS tokens; the engine's running sums
    equal a count of the reference router's choices at the same
    positions, and the gateway samples them as counters."""
    steps = 6
    eng = LLMInstance(CFG, params, V100_LLAMA2_7B, FCFS(), n_slots=3,
                      cache_len=CACHE_LEN)
    rng = np.random.default_rng(2)
    reqs = [Request(prompt_tokens=n, decode_tokens=50,
                    tokens=rng.integers(0, CFG.vocab_size, n).tolist())
            for n in (9, 17, 30)]
    for slot, r in enumerate(reqs):
        eng._admit(r, slot)
    for _ in range(steps):
        eng._decode_iteration()
    assert eng.moe_steps == steps
    rcfg = ref.config_of(CFG)
    w = ref.weights(SEED, rcfg)
    lo, n_held = CFG.moe.held_start, CFG.moe.held
    held = np.zeros((steps, CFG.n_scan_layers, n_held), int)
    for r in reqs:
        seq = list(r.tokens) + r.output_tokens
        routes = _reference_routes(w, rcfg, seq)
        for j in range(steps):
            for layer, experts in enumerate(routes[:, r.prompt_tokens + j]):
                for e in experts:
                    if lo <= e < lo + n_held:
                        held[j, layer, e - lo] += 1
    assert held.sum() > 0
    assert eng.moe_held_pairs == held.sum()
    assert eng.moe_experts_hit == np.count_nonzero(held)

    rec = TraceRecorder()
    gw = Gateway(GatewayConfig(), None, make_gateway_policy("mixing"),
                 cluster=EngineClusterAdapter([eng]), trace=rec)
    gw._sample_counters()
    sampled = {name: v for _, name, v, inst in rec.counters if inst == 0}
    assert sampled["moe_held_pairs"] == eng.moe_held_pairs
    assert sampled["moe_experts_hit"] == eng.moe_experts_hit
