"""Vectorized simulator core: decision-for-decision parity with the
Python stepper, fused batched-RL stepping, O(1) outstanding tokens, and
the gateway's cancellation/autoscaling satellites."""
import numpy as np
import pytest
from _hypothesis_support import example, given, settings, st

from repro.core import batched_rl, rl_router as rl
from repro.core import state as state_lib
from repro.core.policies import make_policy
from repro.core.profiles import A100_LLAMA31_8B, V100_LLAMA2_7B
from repro.core.simulator import Cluster, SimInstance, run_heuristic
from repro.core.vecsim import VecCluster, VecSimPool
from repro.core.workload import (Scenario, SessionConfig, generate,
                                 make_tenant_scenario, scenario_stream,
                                 to_requests)
from repro.serving.gateway import Gateway, GatewayConfig
from repro.serving.policies import make_gateway_policy
from repro.serving.request import Phase, Request
from repro.serving.scheduler import get_scheduler

PROF = V100_LLAMA2_7B


def _reqs(n, seed=0, rate=20.0):
    return to_requests(generate(n, seed=seed), rate=rate, seed=seed + 1)


def _assert_request_parity(ra, rb):
    """Field-level parity between a Python-stepped and a vec-stepped
    copy of the same workload.  Everything except tbt is bit-exact;
    tbt telescopes through synthesized token_times and may differ in
    the last float ulps."""
    for a, b in zip(ra, rb):
        assert a.finished == b.finished, (a.rid, a.finished, b.finished)
        assert a.first_token == b.first_token
        assert a.prefill_done == b.prefill_done
        assert a.routed_at == b.routed_at
        assert a.instance == b.instance
        assert a.preemptions == b.preemptions
        assert a.decoded == b.decoded and a.prefilled == b.prefilled
        assert a.phase is b.phase
        assert len(a.token_times) == len(b.token_times)
        if a.tbt is None:
            assert b.tbt is None
        else:
            assert b.tbt == pytest.approx(a.tbt, rel=1e-12)


# -- seeded heuristic parity -------------------------------------------------

@pytest.mark.parametrize("policy,m,chunk,sched", [
    ("round_robin", 3, 0, "fcfs"),
    ("jsq", 4, 0, "fcfs"),
    ("impact_greedy", 3, 0, "fcfs"),
    ("min_min", 3, 0, "fcfs"),
    ("round_robin", 2, 256, "fcfs"),
    ("round_robin", 3, 0, "bin_packing"),
    ("round_robin", 3, 0, "least_work_left"),
    ("round_robin", 3, 128, "bin_packing"),
])
def test_heuristic_parity(policy, m, chunk, sched):
    ra, rb = _reqs(120, seed=3), _reqs(120, seed=3)
    ca = Cluster(PROF, m, scheduler=sched, chunked_prefill=chunk)
    cb = Cluster(PROF, m, scheduler=sched, chunked_prefill=chunk,
                 backend="vec")
    assert isinstance(cb, VecCluster)
    sa = run_heuristic(ca, ra, make_policy(policy, PROF))
    sb = run_heuristic(cb, rb, make_policy(policy, PROF))
    _assert_request_parity(ra, rb)
    assert sa["spikes"] == sb["spikes"]
    assert sa["e2e_mean"] == sb["e2e_mean"]
    assert sa["ttft_mean"] == sb["ttft_mean"]
    assert len(ca.completed) == len(cb.completed) == 120


def test_heterogeneous_profiles_parity():
    profs = (PROF, A100_LLAMA31_8B)
    ra, rb = _reqs(100, seed=9), _reqs(100, seed=9)
    run_heuristic(Cluster(profs, 2), ra, make_policy("jsq", PROF))
    run_heuristic(Cluster(profs, 2, backend="vec"), rb,
                  make_policy("jsq", PROF))
    _assert_request_parity(ra, rb)


@given(seed=st.integers(0, 40), m=st.integers(1, 5),
       chunk=st.sampled_from([0, 64, 256]))
@settings(max_examples=10, deadline=None)
def test_parity_property(seed, m, chunk):
    """Random widths x chunked-prefill settings: completions, TTFT, and
    preemption counts must match the reference stepper exactly."""
    ra, rb = _reqs(60, seed=seed), _reqs(60, seed=seed)
    run_heuristic(Cluster(PROF, m, chunked_prefill=chunk), ra,
                  make_policy("round_robin", PROF))
    run_heuristic(Cluster(PROF, m, chunked_prefill=chunk, backend="vec"),
                  rb, make_policy("round_robin", PROF))
    _assert_request_parity(ra, rb)


def test_fail_restore_and_elastic_add_parity():
    def drive(backend):
        rs = _reqs(80, seed=11)
        cluster = Cluster(PROF, 3, backend=backend)
        pending = sorted(rs, key=lambda r: r.arrival)
        i, rr, failed, added = 0, 0, False, False
        while len(cluster.completed) < len(rs) and cluster.t < 3000:
            while i < len(pending) and pending[i].arrival <= cluster.t:
                cluster.enqueue(pending[i])
                i += 1
            if cluster.t > 1.0 and not failed:
                cluster.fail_instance(0)
                failed = True
            if cluster.t > 1.5 and not added:
                cluster.add_instance()
                cluster.instances[0].recover()
                cluster.instances[0].clock = cluster.t
                added = True
            alive = cluster.alive()
            while cluster.central and alive:
                cluster.route(alive[rr % len(alive)])
                rr += 1
                alive = cluster.alive()
            cluster.advance()
        assert len(cluster.completed) == len(rs)
        return rs
    a, b = drive("py"), drive("vec")
    _assert_request_parity(a, b)
    # the added instance served on both backends identically
    assert (any(r.instance == 3 for r in a)
            == any(r.instance == 3 for r in b))


@pytest.mark.parametrize("backend", ["vec", "jax"])
@pytest.mark.parametrize("fail_tick", [26, 47, 59])
def test_short_outage_parity(backend, fail_tick):
    """An instance that fails mid-iteration and is recovered a tick
    later: its clock follows the cluster clock while it is down (the
    iteration it was in died with it), so recovery resumes it at the
    cluster time on every backend, clock for clock."""
    def drive(backend):
        rs = to_requests(generate(40, seed=3), rate=30.0, seed=4)
        cluster = Cluster(PROF, 2, backend=backend)
        pending = sorted(rs, key=lambda r: r.arrival)
        i, rr, clocks = 0, 0, []
        while len(cluster.completed) < len(rs) and cluster.t < 500:
            while i < len(pending) and pending[i].arrival <= cluster.t:
                cluster.enqueue(pending[i])
                i += 1
            if len(clocks) == fail_tick:
                cluster.fail_instance(0)
            if len(clocks) == fail_tick + 1:
                cluster.recover_instance(0)
            alive = cluster.alive()
            while cluster.central and alive:
                cluster.route(alive[rr % len(alive)])
                rr += 1
            cluster.advance()
            clocks.append([inst.clock for inst in cluster.instances])
        assert len(cluster.completed) == len(rs)
        return rs, clocks
    (ra, ca), (rb, cb) = drive("py"), drive(backend)
    assert ca == cb
    _assert_request_parity(ra, rb)


# -- featurization / scores read the packed arrays ---------------------------

def test_featurize_bit_exact_against_python_stepper():
    """state.featurize's vec fast path must be bit-identical to the
    scalar path at every decision point of a seeded episode."""
    cfg = rl.RouterConfig(variant="guided", n_instances=3,
                          q_arch="decomposed", seed=0)
    env_p = rl.RoutingEnv(cfg, PROF)
    env_v = rl.RoutingEnv(cfg, PROF, backend="vec")
    s_p = env_p.reset(_reqs(60, seed=5))
    s_v = env_v.reset(_reqs(60, seed=5))
    assert isinstance(env_v.cluster, VecCluster)
    done = False
    steps = 0
    while not done and steps < 400:
        np.testing.assert_array_equal(s_p, s_v)
        np.testing.assert_array_equal(env_p.mask(), env_v.mask())
        np.testing.assert_array_equal(env_p.guidance_bonus(),
                                      env_v.guidance_bonus())
        a = (int(np.argmax(env_p.guidance_bonus()[:env_p.cluster.m]))
             if env_p.cluster.central else env_p.cluster.m)
        s_p, r_p, done, _ = env_p.step(a)
        s_v, r_v, done_v, _ = env_v.step(a)
        assert done == done_v
        assert r_v == pytest.approx(r_p, rel=1e-9, abs=1e-9)
        steps += 1
    assert done


def test_featurize_hardware_block_bit_exact_and_hetero():
    """The optional per-instance hardware block (grad1/grad2/capacity)
    must be bit-identical between the scalar and vec featurize paths,
    distinguish mixed hardware, and zero out on failed instances."""
    profs = (PROF, A100_LLAMA31_8B, PROF)
    cfg = rl.RouterConfig(variant="guided", n_instances=3,
                          q_arch="decomposed", seed=0,
                          include_hardware_features=True)
    env_p = rl.RoutingEnv(cfg, profs)
    env_v = rl.RoutingEnv(cfg, profs, backend="vec")
    s_p = env_p.reset(_reqs(50, seed=5))
    s_v = env_v.reset(_reqs(50, seed=5))
    dims = state_lib.instance_dims(True, True)
    assert s_p.shape[0] == state_lib.state_dim(3, True, True)
    hb = state_lib.INSTANCE_DIMS + 1
    # V100 block vs A100 block carry their own calibration constants
    v100 = s_p[hb:hb + 3]
    a100 = s_p[dims + hb:dims + hb + 3]
    np.testing.assert_allclose(
        v100, [PROF.grad1 * state_lib.HW_G1_SCALE,
               PROF.grad2 * state_lib.HW_G2_SCALE,
               PROF.capacity_tokens * state_lib.HW_CAP_SCALE],
        rtol=1e-7)
    assert not np.array_equal(v100, a100)
    done, steps = False, 0
    while not done and steps < 200:
        np.testing.assert_array_equal(s_p, s_v)
        a = (int(np.argmax(env_p.guidance_bonus()[:3]))
             if env_p.cluster.central else 3)
        s_p, _, done, _ = env_p.step(a)
        s_v, _, done_v, _ = env_v.step(a)
        assert done == done_v
        steps += 1
    assert done
    # failed instance: the whole block (hardware included) reads zero
    env_p.cluster.fail_instance(1)
    s_fail = env_p._state()
    np.testing.assert_array_equal(s_fail[dims:2 * dims],
                                  np.zeros(dims, np.float32))


def test_featurize_vec_many_hardware_matches_single():
    pool = VecSimPool(2)
    cfg = rl.RouterConfig(variant="guided", n_instances=2, seed=0,
                          include_hardware_features=True)
    profs = (PROF, A100_LLAMA31_8B)
    envs = [rl.RoutingEnv(cfg, profs, pool=pool, pool_ep=i)
            for i in range(2)]
    for i, env in enumerate(envs):
        env.reset(_reqs(30, seed=20 + i))
    for _ in range(25):
        for env in envs:
            a = (int(np.argmax(env.guidance_bonus()[:env.cluster.m]))
                 if env.cluster.central else env.cluster.m)
            env.step(a)
        many = state_lib.featurize_vec_many(
            [e.cluster for e in envs], [e.profile for e in envs],
            [e.predict_decode for e in envs],
            n_buckets=cfg.n_buckets, include_impact=True,
            alpha=cfg.alpha, include_hardware=True)
        for env, got in zip(envs, many):
            np.testing.assert_array_equal(got, env._state())


def test_backlog_accounting_drains_to_zero_on_vec():
    cfg = rl.RouterConfig(variant="guided", n_instances=2, seed=0)
    env = rl.RoutingEnv(cfg, PROF, backend="vec")
    env.reset(_reqs(40, seed=9))
    done, added = False, False
    for _ in range(5000):
        if not done:
            a = (int(np.argmax(env.guidance_bonus()[:env.cluster.m]))
                 if env.cluster.central else env.cluster.m)
            _, _, done, _ = env.step(a)
        if not added and env.cluster.t > 1.0:
            env.cluster.add_instance()
            added = True
        if done:
            break
    assert done and added
    assert env._backlog_penalty() == pytest.approx(0.0, abs=1e-9)


# -- batched RL: fused cross-episode stepping --------------------------------

def test_evaluate_scenarios_vec_matches_sequential():
    cfg = rl.RouterConfig(variant="guided", n_instances=3,
                          q_arch="decomposed", seed=0)
    agent = rl.make_agent(cfg)
    ra, rb = _reqs(120, seed=7), _reqs(120, seed=7)
    seq = rl.evaluate(cfg, PROF, agent, ra)
    bat = batched_rl.evaluate_scenarios(
        cfg, agent, [Scenario.homogeneous(PROF, 3, rb)],
        backend="vec")[0]
    _assert_request_parity(ra, rb)
    for key in ("e2e_mean", "ttft_mean", "makespan", "preemptions",
                "router_wait_mean", "spikes"):
        assert seq[key] == pytest.approx(bat[key], rel=1e-9), key


def test_train_batched_vec_reproduces_python_backend():
    """Same seeds, same scenarios: the fused vec trainer must make the
    SAME decisions as the Python-stepper trainer (identical ticks and
    completions; rewards match to float summation order)."""
    def scenario(ep):
        return Scenario.homogeneous(PROF, 3, _reqs(60, seed=300 + ep))

    def cfg():
        return rl.RouterConfig(variant="guided", n_instances=3,
                               explore_episodes=3, q_arch="decomposed",
                               seed=0)
    out_py = batched_rl.train_batched(
        cfg(), scenario, 5,
        bcfg=batched_rl.BatchedRLConfig(n_envs=3, m_max=3,
                                        backend="py"))
    out_vec = batched_rl.train_batched(
        cfg(), scenario, 5,
        bcfg=batched_rl.BatchedRLConfig(n_envs=3, m_max=3,
                                        backend="vec"))
    for hp, hv in zip(out_py["history"], out_vec["history"]):
        assert hp["n"] == hv["n"] == 60
        assert hp["ticks"] == hv["ticks"]
        assert hp["preemptions"] == hv["preemptions"]
        assert hp["e2e_mean"] == pytest.approx(hv["e2e_mean"], rel=1e-9)
        assert hp["reward"] == pytest.approx(hv["reward"], rel=1e-6)


def test_train_batched_vec_hetero_stream_completes():
    cfg = rl.RouterConfig(variant="guided", n_instances=4,
                          explore_episodes=4, q_arch="decomposed", seed=0)
    out = batched_rl.train_batched(
        cfg, scenario_stream(0, n_requests=40), 5,
        bcfg=batched_rl.BatchedRLConfig(n_envs=3, m_max=6,
                                        backend="vec"))
    assert [h["n"] for h in out["history"]] == [40] * 5
    assert out["agent"].buffer.size > 0
    assert len({(h["m"], h["pattern"]) for h in out["history"]}) > 1


def test_featurize_vec_many_matches_single():
    pool = VecSimPool(2)
    cfg = rl.RouterConfig(variant="guided", n_instances=3, seed=0)
    envs = [rl.RoutingEnv(cfg, PROF, pool=pool, pool_ep=i)
            for i in range(2)]
    for i, env in enumerate(envs):
        env.reset(_reqs(40, seed=20 + i))
    for _ in range(40):
        for env in envs:
            a = (int(np.argmax(env.guidance_bonus()[:env.cluster.m]))
                 if env.cluster.central else env.cluster.m)
            env.step(a)
        many = state_lib.featurize_vec_many(
            [e.cluster for e in envs], [e.profile for e in envs],
            [e.predict_decode for e in envs],
            n_buckets=cfg.n_buckets, include_impact=True,
            alpha=cfg.alpha)
        for env, got in zip(envs, many):
            np.testing.assert_array_equal(got, env._state())


# -- O(1) outstanding tokens -------------------------------------------------

def test_outstanding_tokens_incremental_matches_rescan():
    inst = SimInstance(PROF, get_scheduler("fcfs"), 0)
    for r in _reqs(40, seed=3, rate=200.0):
        inst.submit(r)
    for _ in range(3000):
        inst.run_until(inst.clock + 0.02)
        expect = sum((r.prompt_tokens - r.prefilled)
                     + max(r.decode_tokens - r.decoded, 0)
                     for r in inst.residents)
        expect += sum(r.prompt_tokens + r.decode_tokens
                      for r in inst.queue)
        assert inst.outstanding_tokens() == pytest.approx(expect)
        if len(inst.completed) == 40:
            break
    assert len(inst.completed) == 40
    assert inst.outstanding_tokens() == pytest.approx(0.0)


def test_outstanding_tokens_vec_view_matches_python():
    ra, rb = _reqs(60, seed=13), _reqs(60, seed=13)
    ca = Cluster(PROF, 2)
    cb = Cluster(PROF, 2, backend="vec")
    pa = sorted(ra, key=lambda r: r.arrival)
    pb = sorted(rb, key=lambda r: r.arrival)
    ia = ib = 0
    for tick in range(8000):
        for cluster, pending, idx in ((ca, pa, "a"), (cb, pb, "b")):
            i = ia if idx == "a" else ib
            while i < len(pending) and pending[i].arrival <= cluster.t:
                cluster.enqueue(pending[i])
                i += 1
            if idx == "a":
                ia = i
            else:
                ib = i
            while cluster.central:
                cluster.route(tick % 2)
            cluster.advance()
        for k in range(2):
            assert (ca.instances[k].outstanding_tokens()
                    == cb.instances[k].outstanding_tokens())
        if len(ca.completed) == 60 and len(cb.completed) == 60:
            break
    assert len(ca.completed) == len(cb.completed) == 60


# -- gateway satellites: cancellation + autoscaling --------------------------

def _sat_scenario(seed=7, n=120):
    return make_tenant_scenario(seed=seed, n_requests=n, rate=40.0,
                                pattern="bursty",
                                profiles=(PROF,) * 2)


def test_deferred_requests_past_deadline_are_cancelled():
    scn = make_tenant_scenario(seed=7, n_requests=200, rate=80.0,
                               pattern="bursty", profiles=(PROF,) * 2)
    gw = Gateway(GatewayConfig(queue_cap=2, on_full="defer",
                               default_deadline_s=1.0),
                 (PROF,) * 2, make_gateway_policy("rr"))
    stats = gw.run(scn)
    assert stats["cancelled"] > 0
    assert stats["cancelled"] == len(gw.cancelled)
    for r in gw.cancelled:
        assert r.phase is Phase.CANCELLED
        assert r.finished is None
    # cancelled requests surface in the metrics snapshot, per tenant too
    snap = stats["snapshot"]
    assert snap["cancelled"] == stats["cancelled"]
    assert sum(t["cancelled"] for t in snap["tenants"].values()) \
        == stats["cancelled"]
    # nothing cancelled ever completed, and the books balance
    assert stats["admitted"] + stats["shed"] + stats["cancelled"] \
        + len(gw._overflow) == len(scn.requests)


def test_request_level_deadline_beats_default():
    reqs = [Request(prompt_tokens=50, decode_tokens=20,
                    arrival=0.01 * i, deadline=0.5) for i in range(40)]
    gw = Gateway(GatewayConfig(queue_cap=1, on_full="defer"),
                 (PROF,) * 1, make_gateway_policy("rr"))
    stats = gw.run(reqs)
    assert stats["cancelled"] > 0


def test_no_deadline_means_no_cancellation():
    scn = _sat_scenario()
    gw = Gateway(GatewayConfig(queue_cap=4, on_full="defer"),
                 (PROF,) * 2, make_gateway_policy("rr"))
    stats = gw.run(scn)
    assert stats["cancelled"] == 0


def test_autoscale_hook_fires_at_most_once_per_window():
    scn = _sat_scenario(n=200)
    calls = []

    def pred(shed_rate, p95):
        calls.append((shed_rate, p95))
        return True                      # always want more capacity
    gw = Gateway(GatewayConfig(queue_cap=2, on_full="shed",
                               scale_window=10.0),
                 (PROF,) * 2, make_gateway_policy("rr"),
                 scale_up_when=pred)
    stats = gw.run(scn)
    assert stats["scaled"] == len(gw.scale_events) >= 1
    assert gw.cluster.m == 2 + stats["scaled"]
    # rate limit: consecutive scale-ups at least scale_window apart
    for a, b in zip(gw.scale_events, gw.scale_events[1:]):
        assert b - a >= 10.0
    assert calls, "predicate was never consulted"


def test_add_instance_under_load_keeps_parity():
    """Regression: mid-episode scale-out must lower the episode's
    cached min-clock bound, or the advance() fast path skips stepping
    the new lane and decisions diverge from the Python stepper."""
    for seed in (0, 3, 5):
        ra, rb = _reqs(80, seed=seed, rate=30.0), _reqs(80, seed=seed,
                                                        rate=30.0)
        for rs, backend in ((ra, "py"), (rb, "vec")):
            cluster = Cluster(PROF, 2, backend=backend)
            pol = make_policy("jsq", PROF)
            pending = sorted(rs, key=lambda r: r.arrival)
            i, added = 0, False
            while len(cluster.completed) < len(rs) and cluster.t < 3000:
                while (i < len(pending)
                       and pending[i].arrival <= cluster.t):
                    cluster.enqueue(pending[i])
                    i += 1
                if not added and cluster.t > 0.7:
                    cluster.add_instance()
                    added = True
                while cluster.central:
                    a = pol.act(cluster)
                    if a is None or a >= cluster.m:
                        break
                    cluster.route(a)
                cluster.advance()
        _assert_request_parity(ra, rb)


def test_autoscale_predicate_sees_float_p95_before_completions():
    """Regression: the windowed P95 is None before any completion; the
    documented numeric predicates must not crash on the first ticks."""
    scn = _sat_scenario()
    gw = Gateway(GatewayConfig(queue_cap=2, on_full="shed",
                               scale_window=5.0),
                 (PROF,) * 2, make_gateway_policy("rr"),
                 scale_up_when=lambda shed, p95: p95 > 30.0)
    stats = gw.run(scn)            # must not raise
    assert stats["scaled"] == len(gw.scale_events)


def test_autoscale_predicate_false_never_scales():
    scn = _sat_scenario()
    gw = Gateway(GatewayConfig(queue_cap=2, on_full="shed"),
                 (PROF,) * 2, make_gateway_policy("rr"),
                 scale_up_when=lambda shed, p95: False)
    gw.run(scn)
    assert gw.cluster.m == 2 and not gw.scale_events


def test_gateway_rides_vec_backend_with_identical_results():
    scn_a, scn_b = _sat_scenario(seed=3), _sat_scenario(seed=3)
    out = []
    for scn, backend in ((scn_a, "py"), (scn_b, "vec")):
        gw = Gateway(GatewayConfig(queue_cap=8, on_full="defer",
                                   backend=backend),
                     (PROF,) * 2, make_gateway_policy("mixing"))
        out.append(gw.run(scn))
    _assert_request_parity(scn_a.requests, scn_b.requests)
    assert out[0]["shed"] == out[1]["shed"]
    assert out[0]["admitted"] == out[1]["admitted"]
    p_a = out[0]["snapshot"]["e2e"]["p95"]
    p_b = out[1]["snapshot"]["e2e"]["p95"]
    assert p_a == pytest.approx(p_b, rel=1e-12)


@given(seed=st.integers(0, 30), m=st.integers(1, 4),
       pc_tokens=st.sampled_from([0, 256, 4096]),
       inject_failure=st.booleans())
@settings(max_examples=10, deadline=None)
@example(seed=0, m=2, pc_tokens=256, inject_failure=True)
def test_session_cache_parity_property(seed, m, pc_tokens,
                                       inject_failure):
    """Randomized multi-turn session streams through the prefix-cache
    model: cached-prefill admission credit, completion-time radix
    inserts, LRU evictions (the 256-token budget forces them), and
    failure-time cache wipes must be bit-identical across steppers."""
    def drive(backend):
        scn = make_tenant_scenario(seed=seed, n_requests=100,
                                   rate=24.0, pattern="poisson",
                                   profiles=(PROF,) * max(m, 1),
                                   sessions=SessionConfig(block=16))
        rs = scn.requests
        cluster = Cluster(PROF, m, backend=backend,
                          prefix_cache_tokens=pc_tokens,
                          prefix_block=16)
        pending = sorted(rs, key=lambda r: r.arrival)
        i, rr, failed = 0, 0, False
        while len(cluster.completed) < len(rs) and cluster.t < 3000:
            while i < len(pending) and pending[i].arrival <= cluster.t:
                cluster.enqueue(pending[i])
                i += 1
            if inject_failure and m > 1 and not failed \
                    and cluster.t > 1.0:
                cluster.fail_instance(0)
                failed = True
            if failed and cluster.t > 1.5:
                cluster.instances[0].recover()
                cluster.instances[0].clock = cluster.t
                failed = False
            alive = cluster.alive()
            while cluster.central and alive:
                cluster.route(alive[rr % len(alive)])
                rr += 1
                alive = cluster.alive()
            cluster.advance()
        assert len(cluster.completed) == len(rs)
        return rs, cluster
    (ra, ca), (rb, cb) = drive("py"), drive("vec")
    _assert_request_parity(ra, rb)
    for a, b in zip(ra, rb):
        assert a.cached_prefix == b.cached_prefix
    for ia, ib in zip(ca.instances, cb.instances):
        pa = getattr(ia, "prefix_cache", None)
        pb = getattr(ib, "prefix_cache", None)
        assert (pa is None) == (pb is None)
        if pa is not None:
            assert pa.hit_tokens == pb.hit_tokens
            assert pa.lookup_tokens == pb.lookup_tokens
            assert list(pa._blocks) == list(pb._blocks)
