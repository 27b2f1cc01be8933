"""Device-resident jitted backend (core.jaxsim): decision/clock/TTFT
bit-parity with the py/vec steppers across schedulers, chunked prefill
and failure lanes; the shared pool under the batched trainer; the
on-device featurize twin; and the packed replay-row path."""
from types import SimpleNamespace

import numpy as np
import pytest
from _hypothesis_support import given, settings, st
from test_vecsim import _assert_request_parity, _reqs

from repro.core import batched_rl, rl_router as rl
from repro.core import state as state_lib
from repro.core.dqn import DQNConfig, ReplayBuffer
from repro.core.jaxsim import JaxSimPool
from repro.core.policies import make_policy
from repro.core.profiles import V100_LLAMA2_7B
from repro.core.simulator import Cluster, run_heuristic
from repro.core.vecsim import VecCluster
from repro.core.workload import Scenario

PROF = V100_LLAMA2_7B


def _jax_cluster(m, **kw):
    # min_span_ticks=0 sends EVERY span through the jitted kernel (the
    # hybrid default keeps short spans on the numpy path for speed)
    return VecCluster(PROF, m, pool=JaxSimPool(1, min_span_ticks=0),
                      **kw)


# -- seeded heuristic parity: jax kernel vs python stepper -------------------

@pytest.mark.parametrize("chunk,sched", [
    (0, "fcfs"),
    (64, "fcfs"),
    (0, "bin_packing"),
    (128, "least_work_left"),
])
def test_jax_heuristic_parity(chunk, sched):
    ra, rb = _reqs(100, seed=5), _reqs(100, seed=5)
    ca = Cluster(PROF, 3, scheduler=sched, chunked_prefill=chunk)
    cb = _jax_cluster(3, scheduler=sched, chunked_prefill=chunk)
    sa = run_heuristic(ca, ra, make_policy("round_robin", PROF))
    sb = run_heuristic(cb, rb, make_policy("round_robin", PROF))
    _assert_request_parity(ra, rb)
    assert sa["spikes"] == sb["spikes"]
    assert sa["e2e_mean"] == sb["e2e_mean"]
    assert sa["ttft_mean"] == sb["ttft_mean"]
    assert cb.pool.n_jax_calls > 0      # the kernel actually ran


@given(seed=st.integers(0, 30), m=st.integers(1, 4),
       chunk=st.sampled_from([0, 64, 256]), fail=st.booleans())
@settings(max_examples=8, deadline=None)
def test_jax_parity_property(seed, m, chunk, fail):
    """Random widths x chunked-prefill x failure lanes: completions,
    clocks, TTFT and preemption counts must match the reference
    stepper exactly (the py-vs-vec contract, now including jax)."""
    do_fail = fail and m > 1

    def drive(make_cluster):
        rs = _reqs(50, seed=seed)
        cluster = make_cluster()
        pending = sorted(rs, key=lambda r: r.arrival)
        i, rr, failed, restored = 0, 0, False, False
        while len(cluster.completed) < len(rs) and cluster.t < 3000:
            while i < len(pending) and pending[i].arrival <= cluster.t:
                cluster.enqueue(pending[i])
                i += 1
            if do_fail and cluster.t > 1.0 and not failed:
                cluster.fail_instance(0)
                failed = True
            if do_fail and cluster.t > 2.0 and not restored:
                cluster.instances[0].recover()
                cluster.instances[0].clock = cluster.t
                restored = True
            alive = cluster.alive()
            while cluster.central and alive:
                cluster.route(alive[rr % len(alive)])
                rr += 1
                alive = cluster.alive()
            cluster.advance()
        if getattr(cluster, "is_vec", False):
            cluster.sync_all()
        return rs

    a = drive(lambda: Cluster(PROF, m, chunked_prefill=chunk))
    b = drive(lambda: _jax_cluster(m, chunked_prefill=chunk))
    _assert_request_parity(a, b)


# -- batched trainer on the jax pool -----------------------------------------

def test_train_batched_jax_reproduces_python_backend():
    """Same seeds, same scenarios: the jax-pool trainer must make the
    SAME decisions as the Python-stepper trainer (identical ticks and
    completions; rewards match to float summation order)."""
    def scenario(ep):
        return Scenario.homogeneous(PROF, 3, _reqs(40, seed=700 + ep))

    def cfg():
        return rl.RouterConfig(variant="guided", n_instances=3,
                               explore_episodes=2, q_arch="decomposed",
                               seed=0)
    out_py = batched_rl.train_batched(
        cfg(), scenario, 3,
        bcfg=batched_rl.BatchedRLConfig(n_envs=3, m_max=3,
                                        backend="py"))
    out_jax = batched_rl.train_batched(
        cfg(), scenario, 3,
        bcfg=batched_rl.BatchedRLConfig(n_envs=3, m_max=3,
                                        backend="jax"))
    for hp, hj in zip(out_py["history"], out_jax["history"]):
        assert hp["n"] == hj["n"] == 40
        assert hp["ticks"] == hj["ticks"]
        assert hp["preemptions"] == hj["preemptions"]
        assert hp["e2e_mean"] == pytest.approx(hj["e2e_mean"], rel=1e-9)
        assert hp["reward"] == pytest.approx(hj["reward"], rel=1e-6)


# -- on-device featurization -------------------------------------------------

@pytest.mark.parametrize("flags", [
    {},
    {"include_hardware": True},
    {"include_cache": True, "include_health": True},
    {"include_impact": False, "include_hardware": True},
])
def test_featurize_jax_many_bit_parity(flags):
    """The jitted featurize twin must be bit-identical to the numpy
    fast path at every decision point of a seeded episode pair."""
    pool = JaxSimPool(2, min_span_ticks=0)
    cfg = rl.RouterConfig(variant="guided", n_instances=3, seed=0)
    envs = [rl.RoutingEnv(cfg, PROF, pool=pool, pool_ep=i)
            for i in range(2)]
    for i, env in enumerate(envs):
        env.reset(_reqs(30, seed=40 + i))
    for _ in range(25):
        for env in envs:
            a = (int(np.argmax(env.guidance_bonus()[:env.cluster.m]))
                 if env.cluster.central else env.cluster.m)
            env.step(a)
        kw = dict(n_buckets=cfg.n_buckets, alpha=cfg.alpha, **flags)
        vec = state_lib.featurize_vec_many(
            [e.cluster for e in envs], [e.profile for e in envs],
            [e.predict_decode for e in envs], **kw)
        dev = state_lib.featurize_jax_many(
            [e.cluster for e in envs], [e.profile for e in envs],
            [e.predict_decode for e in envs], **kw)
        np.testing.assert_array_equal(dev, vec)


# -- packed replay rows ------------------------------------------------------

def test_packed_replay_rows_bit_identical():
    """ReplayBuffer.add_rows over the jitted packer must leave the
    buffer in EXACTLY the state of per-transition ``add`` calls --
    data, priorities, ring pointer and write sequence -- including
    across a ring wrap and uneven per-round batch sizes."""
    rng = np.random.default_rng(3)
    cfg = DQNConfig(state_dim=6, n_actions=3, buffer_size=32)
    ba, bb = ReplayBuffer(cfg), ReplayBuffer(cfg)
    trans = [(rng.standard_normal(6).astype(np.float32),
              int(rng.integers(0, 3)),
              float(rng.standard_normal()),
              rng.standard_normal(6).astype(np.float32),
              float(rng.integers(0, 2)),
              rng.integers(0, 2, size=3).astype(bool))
             for _ in range(40)]                  # 40 > cap: ring wraps
    for t in trans:
        ba.add(*t)
    stub = SimpleNamespace(cfg=SimpleNamespace(center_rewards=False),
                           buffer=bb)
    i = 0
    for size in (7, 5, 12, 9, 7):                 # uneven round batches
        batched_rl._observe_packed(stub, trans[i:i + size])
        i += size
    np.testing.assert_array_equal(ba.data, bb.data)
    np.testing.assert_array_equal(ba.write_seq, bb.write_seq)
    np.testing.assert_array_equal(ba.prio, bb.prio)
    assert (ba.ptr, ba.size, ba.seq) == (bb.ptr, bb.size, bb.seq)


def test_packed_replay_rows_center_rewards_falls_back():
    """Reward centering is an order-dependent EMA applied at observe
    time; the packed path must defer to sequential ``observe``."""
    calls = []
    stub = SimpleNamespace(cfg=SimpleNamespace(center_rewards=True),
                           buffer=None,
                           observe=lambda *t: calls.append(t))
    trans = [(np.zeros(2, np.float32), 0, 1.0,
              np.zeros(2, np.float32), 0.0, np.ones(2, bool))] * 3
    batched_rl._observe_packed(stub, trans)
    assert len(calls) == 3
