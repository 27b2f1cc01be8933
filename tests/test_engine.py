"""Engine replicas at the reduced width on CPU: the Pallas kernels (in
interpret mode) against the jnp path, generated-id recording, the
cache_len guard, device placement and the serving CLI's fleet."""
import argparse
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import V100_LLAMA2_7B
from repro.launch import serve
from repro.models import params as params_lib
from repro.serving.engine import LLMInstance
from repro.serving.request import Request
from repro.serving.scheduler import FCFS

PROF = V100_LLAMA2_7B
CFG = get_config("qwen3-0.6b").reduced()


@pytest.fixture(scope="module")
def params():
    return params_lib.init_params(jax.random.PRNGKey(0), CFG)


def _request(p, d, seed=0):
    toks = np.random.default_rng(seed).integers(0, CFG.vocab_size, p)
    return Request(prompt_tokens=p, decode_tokens=d, tokens=toks.tolist())


def _serve(eng, reqs, max_steps=3000):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_steps):
        eng.step()
        if all(r.finished is not None for r in reqs):
            return
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("prompt", [37, 200])
def test_pallas_interpret_matches_jnp_engine(params, prompt):
    """The engine's prefill and decode steps give the same logits with
    the Pallas kernels (interpret mode here) as with the jnp path, for a
    prompt inside one flash block and one padded past it."""
    engines = [LLMInstance(dataclasses.replace(CFG, use_pallas=pallas),
                           params, PROF, FCFS(), n_slots=2, cache_len=256)
               for pallas in (False, True)]
    toks = np.asarray(_request(prompt, 1).tokens, np.int32)[None]
    outs = []
    for e in engines:
        logits, small = e.prefill_fn(e.params, toks)
        cache = e.insert_fn(e.cache, small, 1)
        rows = [logits[0]]
        for t in (3, 17, 301):
            lg, cache = e.decode_fn(e.params, cache,
                                    np.array([0, t], np.int32))
            rows.append(lg[1])
        outs.append(np.stack([np.asarray(r) for r in rows]))
    np.testing.assert_allclose(outs[1], outs[0], rtol=2e-4, atol=2e-4)


def test_engine_records_generated_ids(params):
    """Every served request carries decode_tokens ids, and they are the
    greedy continuation of its prompt: the same ids as a prefill over
    prompt + ids predicts, position by position."""
    from repro.models import model as model_lib
    eng = LLMInstance(CFG, params, PROF, FCFS(), n_slots=3, cache_len=64)
    reqs = [_request(12, 9, seed=1), _request(20, 5, seed=2)]
    _serve(eng, reqs)
    for r in reqs:
        assert len(r.output_tokens) == r.decode_tokens
        seq = np.asarray([r.tokens + r.output_tokens], np.int32)
        logits, _ = model_lib.forward_train(params, CFG, tokens=seq)
        greedy = np.asarray(logits[0, r.prompt_tokens - 1:-1].argmax(-1))
        assert greedy.tolist() == r.output_tokens


def test_generated_ids_reset_on_preemption(params):
    """A preempted request restarts with no ids, and its final ids are
    the ones it gets when served alone."""
    prof = dataclasses.replace(PROF, capacity_tokens=100)
    eng = LLMInstance(CFG, params, prof, FCFS(), n_slots=3, cache_len=128)
    reqs = [_request(40, 30, seed=s) for s in range(3)]
    for r in reqs:
        eng.submit(r)
    saw_reset = False
    for _ in range(3000):
        eng.step()
        for r in eng.queue:
            if r.preemptions:
                assert r.output_tokens == [] and r.decoded == 0
                saw_reset = True
        if len(eng.completed) == 3:
            break
    assert saw_reset, "the scenario must preempt"
    alone = LLMInstance(CFG, params, PROF, FCFS(), n_slots=3,
                        cache_len=128)
    for r in reqs:
        solo = _request(40, 30, seed=reqs.index(r))
        _serve(alone, [solo])
        assert r.output_tokens == solo.output_tokens


def test_engine_refuses_request_past_cache_len(params):
    eng = LLMInstance(CFG, params, PROF, FCFS(), n_slots=2, cache_len=64)
    with pytest.raises(ValueError, match="cache_len"):
        eng.submit(_request(40, 25))
    assert not eng.queue
    fits = _request(40, 24)
    _serve(eng, [fits])
    assert len(fits.output_tokens) == 24


def test_serve_cli_cache_fits_its_draws():
    args = argparse.Namespace(
        reduced=True, arch="qwen3-0.6b", instances=2, seed=0,
        scheduler="fcfs", prefix_cache=0, prefix_block=32,
        profile_json=None, calibrate=False)
    engines = serve._engines(args)
    reqs = serve.draw_engine_requests(500, CFG.vocab_size, seed=0)
    longest = max(r.prompt_tokens + r.decode_tokens for r in reqs)
    assert all(longest <= e.cache_len for e in engines)
    assert all(len(r.tokens) == r.prompt_tokens for r in reqs)


def test_replicas_sit_on_their_devices(subproc):
    """Replica i's params, cache and results stay on device i, through
    the warm-up and serving."""
    out = subproc("""
        import jax
        from repro.launch.serve import build_engine_fleet, warm_fleet
        from repro.serving.request import Request
        devs = jax.devices()
        fleet = build_engine_fleet('qwen3-0.6b', 4, reduced=True,
                                   n_slots=2, cache_len=64)
        warm_fleet(fleet, [9, 12])
        for i, e in enumerate(fleet):
            assert not e.resident and not e.queue, i
            leaves = jax.tree.leaves((e.params, e.cache))
            assert all(x.devices() == {devs[i]} for x in leaves), i
            r = Request(prompt_tokens=9, decode_tokens=4)
            e.submit(r)
            while r.finished is None:
                e.step()
            assert len(r.output_tokens) == 4
            assert all(x.devices() == {devs[i]}
                       for x in jax.tree.leaves(e.cache)), i
        print('PLACED', len(fleet))
    """, devices=4)
    assert "PLACED 4" in out


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(subproc, monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, stands; otherwise the cache
    is <checkout>/.jax_cache."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(serve.CHECKOUT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    out = subproc("""
        import jax
        from repro.launch.serve import use_compile_cache
        print('DIR', use_compile_cache(),
              jax.config.jax_compilation_cache_dir)
    """, devices=1)
    assert out.split("DIR")[1].split() == [want, want]
