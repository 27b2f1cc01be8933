"""End-to-end behaviour tests for the paper's system: real engine +
router, engine failure, router checkpoints, RL plumbing."""
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.core import rl_router as rl
from repro.core.profiles import V100_LLAMA2_7B
from repro.core.workload import generate, to_requests
from repro.models import params as params_lib
from repro.serving.engine import LLMInstance
from repro.serving.policies import restore_rl_policy
from repro.serving.request import Request, summarize
from repro.serving.scheduler import FCFS
from repro.training.checkpoint import CheckpointManager

PROF = V100_LLAMA2_7B


def test_real_engine_continuous_batching_and_preemption():
    cfg = get_config("llama-2-7b").reduced()
    prof = dataclasses.replace(PROF, capacity_tokens=220)
    params = params_lib.init_params(jax.random.PRNGKey(0), cfg)
    eng = LLMInstance(cfg, params, prof, FCFS(), n_slots=3, cache_len=128)
    reqs = [Request(prompt_tokens=40, decode_tokens=80),
            Request(prompt_tokens=30, decode_tokens=70),
            Request(prompt_tokens=50, decode_tokens=60)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3000):
        eng.step()
        if len(eng.completed) == 3:
            break
    assert len(eng.completed) == 3
    stats = summarize(reqs)
    assert stats["e2e_mean"] > 0
    # continuous batching: decode phases overlapped (makespan < serial sum)
    serial = sum(prof.request_time(r.prompt_tokens, r.decode_tokens)
                 for r in reqs)
    assert stats["makespan"] < serial


def test_engine_failure_requeues():
    cfg = get_config("llama-2-7b").reduced()
    params = params_lib.init_params(jax.random.PRNGKey(0), cfg)
    eng = LLMInstance(cfg, params, PROF, FCFS(), n_slots=2, cache_len=64)
    reqs = [Request(prompt_tokens=10, decode_tokens=30) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    orphans = eng.fail()
    assert len(orphans) == 3
    assert all(r.instance is None and r.decoded == 0 for r in orphans)
    assert eng.step() == []          # dead engine does nothing


def test_router_checkpoint_roundtrip(tmp_path):
    """A router saved with CheckpointManager comes back through
    restore_rl_policy, the path ``--policy rl --checkpoint`` serves."""
    cfg = rl.RouterConfig(variant="guided", n_instances=2,
                          q_arch="decomposed", seed=3)
    agent = rl.make_agent(cfg)
    CheckpointManager(str(tmp_path)).save(7, agent.state_dict(),
                                          sync=True)
    policy = restore_rl_policy(dataclasses.replace(cfg, seed=99),
                               str(tmp_path), m=2)
    for a, b in zip(jax.tree.leaves(agent.params),
                    jax.tree.leaves(policy.agent.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rl_training_smoke():
    """RL loop runs end to end and the guided variant's guidance decays."""
    cfg = rl.RouterConfig(variant="guided", n_instances=2,
                          explore_episodes=2, q_arch="decomposed", seed=0)
    out = rl.train(
        cfg, PROF,
        lambda ep: to_requests(generate(60, seed=ep), rate=20.0,
                               seed=ep + 9),
        n_episodes=3)
    hist = out["history"]
    assert len(hist) == 3
    assert hist[0]["guide_w"] > hist[-1]["guide_w"]
    st = rl.evaluate(cfg, PROF, out["agent"],
                     to_requests(generate(60, seed=77), rate=20.0,
                                 seed=78))
    assert st["n"] == 60
