"""Online continual learning: drift scenario determinism, py-vs-vec
transition parity, frozen-policy equivalence, full learner-state
checkpoint round-trips, atomic hot-swap, the safe-fallback guardrail,
and the saturating preemption-fidelity stream."""
import threading

import jax
import numpy as np
import pytest

from repro.core import dqn as dqn_lib
from repro.core import rl_router as rl
from repro.core import workload as wl
from repro.core.profiles import V100_LLAMA2_7B
from repro.serving import fidelity as fid
from repro.serving.gateway import Gateway, GatewayConfig, OracleLength
from repro.serving.policies import RLPolicy
from repro.training.checkpoint import (CheckpointManager, restore_learner,
                                       save_learner)
from repro.training.online import OnlineConfig, OnlinePolicy, OnlineTrainer

PROF = V100_LLAMA2_7B


def _rcfg(m=3, **kw):
    kw.setdefault("include_health_features", True)
    return rl.RouterConfig(variant="guided", n_instances=m,
                           q_arch="decomposed", seed=0, **kw)


def _req_key(r):
    return (r.prompt_tokens, r.decode_tokens, r.arrival, r.tenant, r.task)


# -- drift scenario generator ------------------------------------------------

def test_drift_scenario_deterministic():
    a = wl.make_drift_scenario(seed=11, n_requests=200)
    b = wl.make_drift_scenario(seed=11, n_requests=200)
    assert [_req_key(r) for r in a.requests] \
        == [_req_key(r) for r in b.requests]
    assert a.meta["chaos"] == b.meta["chaos"]     # frozen dataclasses
    assert a.meta["flip_time"] == b.meta["flip_time"]
    # a different seed moves the stream
    c = wl.make_drift_scenario(seed=12, n_requests=200)
    assert [_req_key(r) for r in a.requests] \
        != [_req_key(r) for r in c.requests]


def test_drift_scenario_flips_mix_and_churns_tenants():
    scn = wl.make_drift_scenario(seed=5, n_requests=300, flip_frac=0.5)
    i = scn.meta["flip_index"]
    pre = {r.tenant for r in scn.requests[:i]}
    post = {r.tenant for r in scn.requests[i:]}
    assert "chat" in pre and "chat" not in post       # tenant leaves
    assert "ingest" in post and "ingest" not in pre   # tenant arrives
    # arrivals are one continuous stream: monotone across the flip
    ts = [r.arrival for r in scn.requests]
    assert ts == sorted(ts)
    assert scn.requests[i].arrival == pytest.approx(scn.meta["flip_time"])
    # the auto chaos straggles an instance from the flip onward
    ch = scn.meta["chaos"]
    assert ch.stragglers and ch.stragglers[0].t0 == scn.meta["flip_time"]
    assert ch.crashes and ch.crashes[0].restart_after is not None
    # chaos=None leaves a pure workload flip
    assert wl.make_drift_scenario(seed=5, n_requests=60,
                                  chaos=None).meta["chaos"] is None


# -- transition recording ----------------------------------------------------

def _spy_trainer(rcfg, **ocfg_kw):
    tr = OnlineTrainer(rcfg, OnlineConfig(**ocfg_kw))
    rows = []
    orig = tr._pack

    def spy(t, s2, mask2, done=1.0):
        rows.append((np.array(t[0]), int(t[1]), float(t[2]),
                     np.array(s2), np.array(mask2), float(done)))
        orig(t, s2, mask2, done)
    tr._pack = spy
    return tr, rows


def _run_online(backend, rcfg, scn, seed=0, learn=False, **gw_kw):
    tr, rows = _spy_trainer(rcfg, learn=learn, eps=0.0, guard=False,
                            seed=seed)
    gw = Gateway(GatewayConfig(backend=backend, **gw_kw),
                 scn.profiles, tr.policy, length=OracleLength())
    stats = gw.run(_clone(scn.requests))
    return stats, rows, tr


def _clone(reqs):
    from repro.serving.request import Request
    return [Request(prompt_tokens=r.prompt_tokens,
                    decode_tokens=r.decode_tokens, arrival=r.arrival,
                    task=r.task, tenant=r.tenant) for r in reqs]


def test_online_transition_parity_py_vs_vec_under_drift():
    """Bit parity of the recorded transition stream between the python
    stepper and the vectorized backend, on the drift scenario WITH the
    mid-stream flip and instance fail/recover active.  States, actions,
    masks, and done flags are bit-exact; rewards agree to float
    tolerance (the vec backlog accumulators sum via np.bincount, a
    documented summation-order divergence)."""
    rcfg = _rcfg()
    scn = wl.make_drift_scenario(seed=9, n_requests=160, rate=14.0,
                                 profiles=(PROF,) * 3)
    out = {}
    for backend in ("py", "vec"):
        stats, rows, _ = _run_online(backend, rcfg, scn,
                                     chaos=scn.meta["chaos"],
                                     failover=True)
        out[backend] = (stats, rows)
    (sp, rp), (sv, rv) = out["py"], out["vec"]
    assert sp["n"] == sv["n"] > 0
    assert sp["orphaned"] == sv["orphaned"] > 0    # the crash really hit
    assert len(rp) == len(rv) > 0
    for a, b in zip(rp, rv):
        np.testing.assert_array_equal(a[0], b[0])      # s
        np.testing.assert_array_equal(a[3], b[3])      # s2
        np.testing.assert_array_equal(a[4], b[4])      # mask2
        assert a[1] == b[1] and a[5] == b[5]           # action, done
        assert a[2] == pytest.approx(b[2], rel=1e-6, abs=1e-9)


def test_online_transitions_deterministic():
    """Same seed, same backend -> byte-identical transition stream."""
    rcfg = _rcfg()
    scn = wl.make_drift_scenario(seed=4, n_requests=100, rate=12.0,
                                 profiles=(PROF,) * 3, chaos=None)
    _, ra, _ = _run_online("py", rcfg, scn)
    _, rb, _ = _run_online("py", rcfg, scn)
    assert len(ra) == len(rb) > 0
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a[0], b[0])
        assert (a[1], a[2], a[5]) == (b[1], b[2], b[5])


def test_online_frozen_equivalence():
    """With learning off, eps=0 and the guardrail off, the online
    policy's decision stream is identical to a frozen RLPolicy over the
    same agent weights -- shadow recording is behaviorally free."""
    rcfg = _rcfg()
    scn = wl.make_tenant_scenario(seed=5, n_requests=120, rate=16.0,
                                  pattern="bursty", profiles=(PROF,) * 3)
    reqs_a, reqs_b = _clone(scn.requests), _clone(scn.requests)
    agent = rl.make_agent(rcfg)
    gw_f = Gateway(GatewayConfig(), scn.profiles, RLPolicy(agent, rcfg),
                   length=OracleLength())
    gw_f.run(reqs_a)
    tr = OnlineTrainer(rcfg, OnlineConfig(learn=False, eps=0.0,
                                          guard=False),
                       agent=rl.make_agent(rcfg))
    gw_o = Gateway(GatewayConfig(), scn.profiles, tr.policy,
                   length=OracleLength())
    gw_o.run(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        assert a.instance == b.instance
        assert a.finished == b.finished
    assert tr.transitions > 0          # ... while still recording


def test_online_learner_steps_and_publishes():
    rcfg = _rcfg()
    scn = wl.make_tenant_scenario(seed=3, n_requests=400, rate=20.0,
                                  pattern="bursty", profiles=(PROF,) * 3)
    tr = OnlineTrainer(rcfg, OnlineConfig(seed=0))
    gw = Gateway(GatewayConfig(), scn.profiles, tr.policy,
                 length=OracleLength())
    stats = gw.run(_clone(scn.requests))
    assert stats["n"] == 400
    assert tr.agent.steps > 0                    # learner actually ran
    assert tr.publishes > 1                      # weights were republished
    assert tr.agent.buffer.size == tr.transitions
    # the served weights are the learner's latest published tree
    assert tr.policy.agent.params is tr.agent.params


def test_online_rejects_engine_backend():
    rcfg = _rcfg(m=1)
    tr = OnlineTrainer(rcfg, m=1)

    class _FakeEngineView:                 # no on_token hook surface
        pass

    class _FakeCluster:
        is_vec = False
        instances = (_FakeEngineView(),)

    class _FakeGateway:
        cluster = _FakeCluster()

    with pytest.raises(ValueError, match="backend"):
        tr.bind(_FakeGateway())


# -- full learner-state checkpointing ----------------------------------------

def _filled_agent(rcfg, seed=0, n=700):
    agent = rl.make_agent(rcfg)
    rng = np.random.default_rng(seed)
    d, na = agent.cfg.state_dim, agent.cfg.n_actions
    for _ in range(n):
        s = rng.normal(size=d).astype(np.float32)
        s2 = rng.normal(size=d).astype(np.float32)
        mask = np.ones(na, bool)
        agent.observe(s, int(rng.integers(na)), float(rng.normal()),
                      s2, 1.0, mask)
    return agent


def _trees_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_full_checkpoint_exact_resume(tmp_path):
    """save_learner/restore_learner round-trips EVERYTHING: params,
    target, optimizer, replay contents+priorities, centering EMA, RNG.
    A restored learner continues bit-identically to the original."""
    rcfg = _rcfg()
    agent = _filled_agent(rcfg)
    for _ in range(3):
        agent.learn(sync=True)
    save_learner(str(tmp_path / "ck"), step=agent.steps, agent=agent)

    fresh = rl.make_agent(rcfg)
    step = restore_learner(str(tmp_path / "ck"), fresh)
    assert step == agent.steps
    _trees_equal(fresh.params, agent.params)
    _trees_equal(fresh.target, agent.target)
    _trees_equal(fresh.opt, agent.opt)
    np.testing.assert_array_equal(fresh.buffer.data, agent.buffer.data)
    np.testing.assert_array_equal(fresh.buffer.prio, agent.buffer.prio)
    assert fresh.buffer.ptr == agent.buffer.ptr
    assert fresh.buffer.size == agent.buffer.size
    assert fresh.r_mean == agent.r_mean
    assert fresh.rng.bit_generator.state == agent.rng.bit_generator.state
    # exact resume: both continue with identical prioritized sampling
    for _ in range(3):
        la = agent.learn(sync=True)
        lb = fresh.learn(sync=True)
        assert la == lb
    _trees_equal(fresh.params, agent.params)
    assert fresh.steps == agent.steps


def test_restore_learner_accepts_params_only_artifact(tmp_path):
    """The offline trainers save bare state_dict trees; restore_learner
    warm-starts networks+optimizer from those and leaves the replay
    buffer fresh."""
    rcfg = _rcfg()
    src = _filled_agent(rcfg, n=600)
    src.learn(sync=True)
    mgr = CheckpointManager(str(tmp_path / "off"))
    mgr.save(7, src.state_dict(), {}, sync=True)
    mgr.close()
    fresh = rl.make_agent(rcfg)
    step = restore_learner(str(tmp_path / "off"), fresh)
    assert step == 7
    _trees_equal(fresh.params, src.params)
    assert fresh.buffer.size == 0                  # replay NOT restored


def test_restore_learner_missing_dir_is_none(tmp_path):
    agent = rl.make_agent(_rcfg())
    assert restore_learner(str(tmp_path / "nope"), agent) is None


def test_online_warm_start_from_offline_checkpoint(tmp_path):
    rcfg = _rcfg()
    src = _filled_agent(rcfg, n=600)
    src.learn(sync=True)
    save_learner(str(tmp_path / "warm"), step=11, agent=src)
    tr = OnlineTrainer(rcfg, OnlineConfig(
        warm_start=str(tmp_path / "warm")))
    assert tr.warm_started_step == 11
    _trees_equal(tr.agent.params, src.params)
    # the published serving weights ARE the warm-started tree
    assert tr.policy.agent.params is tr.agent.params


# -- atomic hot-swap ---------------------------------------------------------

def test_hot_swap_no_torn_reads():
    """A writer thread flips the policy between two tagged param trees
    while a reader evaluates Q continuously: every read must produce
    the exact output of ONE tree, never a torn mixture of layers."""
    rcfg = _rcfg()
    agent = rl.make_agent(rcfg)
    policy = RLPolicy(agent, rcfg)
    tree_a = agent.params
    tree_b = jax.tree.map(lambda x: x + 1.0, tree_a)
    s = np.random.default_rng(0).normal(
        size=agent.cfg.state_dim).astype(np.float32)[None]
    qa = np.asarray(dqn_lib.q_values(agent.cfg, tree_a, s))
    qb = np.asarray(dqn_lib.q_values(agent.cfg, tree_b, s))
    assert not np.allclose(qa, qb)
    stop = threading.Event()

    def writer():
        trees = (tree_a, tree_b)
        i = 0
        while not stop.is_set():
            policy.hot_swap(trees[i & 1])
            i += 1

    torn = []
    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(300):
            q = np.asarray(dqn_lib.q_values(agent.cfg,
                                            policy.agent.params, s))
            if not (np.array_equal(q, qa) or np.array_equal(q, qb)):
                torn.append(q)
    finally:
        stop.set()
        t.join()
    assert not torn, f"torn read detected: {torn[:1]}"


# -- safe-fallback guardrail -------------------------------------------------

def test_guardrail_trips_to_mixing_and_recovers():
    """An adversarial Q-head (argmin of the guidance bonus) must trip
    the regret guardrail; during fallback decisions equal the mixing
    argmax; after the cooldown the trainer re-probes the Q-head."""
    rcfg = _rcfg()
    tr = OnlineTrainer(rcfg, OnlineConfig(
        learn=False, eps=0.0, guard=True, guard_window=8,
        guard_regret=1e-4, guard_cooldown=3.0))

    # sabotage the served Q: always pick the WORST-bonus valid action
    class _Adversary:
        cfg = tr.serve_agent.cfg

        def act(self, s, mask, epsilon=0.0, prior=None, q_squash=0.0):
            bonus = prior if prior is not None else np.zeros(len(mask))
            b = np.where(mask, bonus, np.inf)
            return int(np.argmin(b))
    tr.policy.agent = _Adversary()
    tr.serve_agent = tr.policy.agent

    scn = wl.make_tenant_scenario(seed=2, n_requests=150, rate=16.0,
                                  pattern="bursty", profiles=(PROF,) * 3)
    gw = Gateway(GatewayConfig(), scn.profiles, tr.policy,
                 length=OracleLength())
    stats = gw.run(_clone(scn.requests))
    assert stats["n"] == 150
    assert tr.fallback_entries >= 1          # guardrail tripped
    assert tr.fallback_decisions > 0         # ... and routed by mixing
    # cooldown expired at least once mid-run (trip count > 1 or ended
    # back in rl mode): the fallback is a probation, not a latch
    assert tr.fallback_entries > 1 or tr.mode == "rl"


def test_guardrail_stays_quiet_for_mixing_equivalent_decisions():
    """Decisions that track the guidance argmax accumulate ~zero
    regret: the guardrail must not trip on a healthy policy."""
    rcfg = _rcfg()
    tr = OnlineTrainer(rcfg, OnlineConfig(
        learn=False, eps=0.0, guard=True, guard_window=8,
        guard_regret=0.05))

    class _Mirror:                      # picks the best-bonus action
        cfg = tr.serve_agent.cfg

        def act(self, s, mask, epsilon=0.0, prior=None, q_squash=0.0):
            bonus = prior if prior is not None \
                else np.zeros(len(mask))
            return int(np.argmax(np.where(mask, bonus, -np.inf)))
    tr.policy.agent = _Mirror()
    tr.serve_agent = tr.policy.agent
    scn = wl.make_tenant_scenario(seed=2, n_requests=120, rate=14.0,
                                  pattern="bursty", profiles=(PROF,) * 3)
    gw = Gateway(GatewayConfig(), scn.profiles, tr.policy,
                 length=OracleLength())
    gw.run(_clone(scn.requests))
    assert tr.fallback_entries == 0


# -- saturating preemption-fidelity stream -----------------------------------

def test_saturating_stream_preempts_on_both_simulator_backends():
    fcfg = fid.FidelityConfig(backends=("py", "vec"), n_requests=32,
                              saturate=True)
    rep = fid.run_fidelity(PROF, fcfg)
    py = rep["backends"]["py"]
    assert py["preemptions"] > 0
    assert py["completed"] == 32                  # queued, not lost
    assert rep["backends"]["vec"] == py           # bitwise sim parity
    d = rep["deltas"]["vec_vs_py"]["preemptions"]
    assert d["both_preempt"] and d["abs"] == 0


def test_saturating_stream_is_deterministic_and_clustered():
    fcfg = fid.FidelityConfig(n_requests=16, saturate=True)
    sa = fid.make_stream(fcfg)
    assert sa == fid.make_stream(fcfg)
    # bursts of 2*n_slots near-simultaneous ladder-top prompts
    assert all(p == max(fcfg.prompt_lengths) for p, _, _ in sa)
    g = 2 * fcfg.n_slots
    t0 = [t for _, _, t in sa[:g]]
    assert max(t0) - min(t0) < 0.01


def test_saturating_stream_preempts_on_real_engine():
    """The engine leg of preemption fidelity: same saturating stream,
    tiny real engine, preemptions on BOTH sides of the delta."""
    from repro.configs import get_config
    from repro.models import params as params_lib
    model_cfg = get_config("qwen3-0.6b").reduced()
    params = params_lib.init_params(jax.random.PRNGKey(0), model_cfg)
    fcfg = fid.FidelityConfig(
        backends=("py", "engine"), n_requests=8, n_instances=1,
        n_slots=2, cache_len=64, capacity_tokens=80,
        prompt_lengths=(16, 32), decode_range=(4, 12), rate=6.0,
        saturate=True)
    rep = fid.run_fidelity(PROF, fcfg, model_cfg=model_cfg,
                           params=params)
    d = rep["deltas"]["engine_vs_py"]["preemptions"]
    assert d["both_preempt"], d
    assert rep["backends"]["engine"]["completed"] == 8
