"""Serving gateway: streaming metrics, backpressure, policy parity,
micro-batched length prediction, prioritized replay, trend gate."""
import numpy as np
import pytest

from repro.core import batched_rl, predictor as pred_lib
from repro.core import rl_router as rl
from repro.core import workload as wl
from repro.core.dqn import DQNConfig, ReplayBuffer
from repro.core.profiles import V100_LLAMA2_7B
from repro.serving.gateway import (Gateway, GatewayConfig,
                                   MicroBatchPredictor, OracleLength)
from repro.serving.metrics import SLO, P2Quantile, StreamMetrics, \
    WindowedReservoir
from repro.serving.policies import (LeastOutstandingWork,
                                    MixingImpactPolicy, RLPolicy,
                                    RoundRobinPolicy, make_gateway_policy)
from repro.training.train_loop import train_router

PROF = V100_LLAMA2_7B


def _tiny_predictor(seed=0):
    cfg = pred_lib.PredictorConfig(seq_len=32, d_model=16, n_heads=2,
                                   n_layers=1)
    return pred_lib.BucketPredictor(cfg, PROF, seed=seed)


def _scenario(seed=3, n=120, rate=14.0, m=3, pattern="bursty"):
    return wl.make_tenant_scenario(seed=seed, n_requests=n, rate=rate,
                                   pattern=pattern,
                                   profiles=(PROF,) * m)


# -- streaming percentile estimators ----------------------------------------

def test_p2_quantile_tracks_numpy():
    rng = np.random.default_rng(0)
    for q in (0.5, 0.95, 0.99):
        xs = rng.lognormal(1.0, 0.8, size=4000)
        est = P2Quantile(q)
        for x in xs:
            est.add(float(x))
        exact = float(np.quantile(xs, q))
        # P2 is an approximation; a few percent on a lognormal stream
        assert est.value() == pytest.approx(exact, rel=0.08), q


def test_p2_quantile_exact_below_five_samples():
    est = P2Quantile(0.5)
    for x in (3.0, 1.0, 2.0):
        est.add(x)
    assert est.value() == pytest.approx(2.0)
    assert P2Quantile(0.95).value() is None


def test_windowed_reservoir_matches_numpy_and_evicts():
    rng = np.random.default_rng(1)
    win = WindowedReservoir(window=10.0)
    samples = [(float(t), float(x)) for t, x in
               zip(np.linspace(0, 50, 500), rng.normal(5, 2, 500))]
    for t, x in samples:
        win.add(t, x)
    now = 50.0
    live = np.array([x for t, x in samples if t >= now - 10.0])
    for q in (0.5, 0.95, 0.99):
        assert win.quantile(q, now) == pytest.approx(
            float(np.quantile(live, q)))
    assert len(win) == live.size        # old samples evicted
    assert win.total == 500             # lifetime count preserved


def test_stream_metrics_per_tenant_and_slo():
    from repro.serving.request import Request
    m = StreamMetrics(window=100.0, slo=SLO(ttft_s=None, tbt_s=None,
                                            e2e_s=1.0))
    for i, (tenant, e2e) in enumerate([("a", 0.5), ("a", 2.0),
                                       ("b", 0.2)]):
        r = Request(prompt_tokens=10, decode_tokens=5, arrival=float(i),
                    tenant=tenant)
        r.first_token = r.arrival + e2e / 2
        r.finished = r.arrival + e2e
        m.on_admit(tenant)
        m.on_complete(r, tenant)
    m.on_shed("b")
    snap = m.snapshot(now=10.0)
    assert snap["completed"] == 3 and snap["shed"] == 1
    assert snap["slo_attained"] == 2
    assert snap["tenants"]["a"]["completed"] == 2
    assert snap["tenants"]["b"]["shed"] == 1
    assert snap["e2e"]["p50"] == pytest.approx(0.5)


# -- backpressure ------------------------------------------------------------

def test_bounded_queue_sheds_at_saturation():
    scn = _scenario(n=150, rate=60.0, m=2)
    gw = Gateway(GatewayConfig(queue_cap=8, on_full="shed"),
                 (PROF,) * 2, MixingImpactPolicy())
    stats = gw.run(scn)
    assert stats["shed"] > 0
    assert stats["admitted"] + stats["shed"] == 150
    assert stats["n"] == stats["admitted"]       # admitted all complete
    snap = stats["snapshot"]
    assert snap["shed"] == stats["shed"]
    assert 0.0 < snap["shed_rate"] < 1.0
    from repro.serving.request import Phase
    assert all(r.phase is Phase.SHED for r in gw.shed)


def test_bounded_queue_defers_without_loss():
    scn = _scenario(n=150, rate=60.0, m=2)
    cap = 8
    gw = Gateway(GatewayConfig(queue_cap=cap, on_full="defer"),
                 (PROF,) * 2, MixingImpactPolicy())
    stats = gw.run(scn)
    assert stats["shed"] == 0
    assert stats["n"] == stats["admitted"] == 150   # nothing lost
    # the router queue never exceeded the admission bound
    assert max(gw.cluster.queue_len_trace) <= cap


def test_unbounded_queue_never_sheds():
    scn = _scenario(n=80, rate=30.0, m=2)
    gw = Gateway(GatewayConfig(), (PROF,) * 2, RoundRobinPolicy())
    stats = gw.run(scn)
    assert stats["shed"] == 0 and stats["n"] == 80


# -- policy parity with the closed-loop path ---------------------------------

def test_policy_parity_with_evaluate():
    """Gateway + RL policy + oracle length service + unbounded queue
    must reproduce rl_router.evaluate decision for decision."""
    cfg = rl.RouterConfig(variant="guided", n_instances=3,
                          q_arch="decomposed", seed=0)
    agent = rl.make_agent(cfg)
    reqs_a = wl.to_requests(wl.generate(120, seed=7), rate=20.0, seed=8)
    reqs_b = wl.to_requests(wl.generate(120, seed=7), rate=20.0, seed=8)
    seq = rl.evaluate(cfg, PROF, agent, reqs_a)
    gw = Gateway(GatewayConfig(), (PROF,) * 3, RLPolicy(agent, cfg),
                 length=OracleLength())
    bat = gw.run(reqs_b)
    assert seq["n"] == bat["n"] == 120
    for a, b in zip(reqs_a, reqs_b):
        assert (a.arrival, a.prompt_tokens) == (b.arrival,
                                                b.prompt_tokens)
        assert a.finished == pytest.approx(b.finished, abs=1e-9)
        assert a.instance == b.instance
        assert a.preemptions == b.preemptions
    for key in ("e2e_mean", "ttft_mean", "makespan", "preemptions"):
        assert seq[key] == pytest.approx(bat[key], rel=1e-9), key


def test_all_policies_complete_the_stream():
    for name in ("rr", "jsq", "mixing"):
        scn = _scenario(n=60, rate=10.0, m=2)
        gw = Gateway(GatewayConfig(), (PROF,) * 2,
                     make_gateway_policy(name))
        stats = gw.run(scn)
        assert stats["n"] == 60, name
        assert set(stats["snapshot"]["tenants"]) <= {"chat", "batch",
                                                     "misc"}


def test_jsq_policy_balances_outstanding_work():
    scn = _scenario(n=80, rate=12.0, m=3)
    gw = Gateway(GatewayConfig(), (PROF,) * 3, LeastOutstandingWork())
    stats = gw.run(scn)
    assert stats["n"] == 80
    per_inst = [sum(1 for r in scn.requests if r.instance == i)
                for i in range(3)]
    assert min(per_inst) > 0            # no instance starved


# -- micro-batched length predictor ------------------------------------------

def test_microbatch_predictor_matches_batch_predict_and_caches():
    pred = _tiny_predictor()
    samples = wl.generate(40, seed=5)
    reqs = wl.to_requests(samples, rate=10.0, seed=6)
    svc = MicroBatchPredictor(pred, batch_pad=16)
    svc.prefetch(list(zip(reqs, samples)))
    want = pred.predict(samples)
    got = np.array([r.predicted_bucket for r in reqs])
    np.testing.assert_array_equal(got, want)
    cap = int(PROF.capacity_tokens * 0.95)
    for r, b in zip(reqs, want):
        assert svc.estimate(r) == max(
            min(int(pred.bucket_upper_tokens(int(b))),
                cap - r.prompt_tokens), 1)
    assert svc.forwards == int(np.ceil(40 / 16))   # micro-batched
    assert svc.misses == 40 and svc.hits == 0
    # identical prompt content -> LRU hit, no new forward
    reqs2 = wl.to_requests(samples, rate=10.0, seed=7)
    svc.prefetch(list(zip(reqs2, samples)))
    assert svc.hits == 40 and svc.forwards == int(np.ceil(40 / 16))
    np.testing.assert_array_equal(
        np.array([r.predicted_bucket for r in reqs2]), want)


def test_microbatch_lru_evicts_oldest():
    pred = _tiny_predictor()
    svc = MicroBatchPredictor(pred, batch_pad=8, cache_size=10)
    samples = wl.generate(25, seed=9)
    reqs = wl.to_requests(samples, rate=10.0, seed=10)
    svc.prefetch(list(zip(reqs, samples)))
    assert len(svc._cache) <= 10


def test_gateway_runs_with_predictor_not_oracle():
    scn = _scenario(n=60, rate=10.0, m=2)
    svc = MicroBatchPredictor(_tiny_predictor())
    gw = Gateway(GatewayConfig(), (PROF,) * 2, MixingImpactPolicy(),
                 length=svc)
    stats = gw.run(scn)
    assert stats["n"] == 60
    assert svc.misses + svc.hits == 60
    assert all(r.predicted_decode is not None for r in scn.requests)


# -- predictor-backed d-hat in RL training (PR-1 follow-up) ------------------

def test_train_router_with_length_predictor_in_loop():
    pred = _tiny_predictor()
    seen = []

    def scn_fn(ep):
        samples = wl.generate(30, seed=50 + ep)
        s = wl.Scenario.homogeneous(
            PROF, 2, wl.to_requests(samples, rate=8.0, seed=60 + ep),
            name=f"t{ep}", samples=samples)
        seen.append(s)
        return s

    cfg = rl.RouterConfig(variant="guided", n_instances=2,
                          explore_episodes=2, q_arch="decomposed",
                          seed=0)
    out = train_router(cfg, scn_fn, 2,
                       batch_cfg=batched_rl.BatchedRLConfig(n_envs=2,
                                                            m_max=2),
                       length_predictor=pred)
    assert len(out["history"]) == 2
    for h in out["history"]:
        assert h["n"] == 30
    # every request the trainer saw carried the predictor's d-hat
    for s in seen:
        assert all(r.predicted_decode is not None for r in s.requests)
        assert all(r.predicted_bucket is not None for r in s.requests)
    r = seen[0].requests[0]
    assert pred_lib.predicted_decode(r) == r.predicted_decode


# -- prioritized replay -------------------------------------------------------

def _buf_cfg(**kw):
    base = dict(state_dim=4, n_actions=3, batch_size=8, buffer_size=64)
    base.update(kw)
    return DQNConfig(**base)


def test_replay_row_carries_unit_weight_by_default():
    buf = ReplayBuffer(_buf_cfg())
    buf.add(np.ones(4), 1, 0.5, np.zeros(4), 0.0, np.ones(3))
    assert buf.data.shape[1] == 2 * 4 + 4 + 3     # [s|s2|a|r|done|m|w]
    row = buf.data[0]
    assert row[-1] == 1.0
    np.testing.assert_array_equal(row[2 * 4 + 3:-1], np.ones(3))
    rows = buf.sample(np.random.default_rng(0), 4)
    assert np.all(rows[:, -1] == 1.0)             # uniform fallback


def test_prioritized_sampling_prefers_high_td():
    buf = ReplayBuffer(_buf_cfg())
    rng = np.random.default_rng(0)
    for i in range(32):
        buf.add(np.ones(4) * i, i % 3, 0.0, np.zeros(4), 0.0, np.ones(3))
    buf.update_priorities(np.arange(16), np.full(16, 1e-9))
    counts = np.zeros(32)
    for _ in range(200):
        rows, idx = buf.sample_prioritized(rng, 8, alpha=0.6, beta=0.4)
        assert np.all(rows[:, -1] > 0) and np.all(rows[:, -1] <= 1.0)
        counts[idx] += 1
    assert counts[16:].sum() > 5 * counts[:16].sum()
    # stored rows keep unit weights (IS weight only in the sampled copy)
    assert np.all(buf.data[:32, -1] == 1.0)


def test_per_priority_update_skips_overwritten_slots():
    """A deferred priority update for a ring slot that has since been
    overwritten must be dropped -- the fresh transition keeps its
    max-priority first-replay guarantee."""
    buf = ReplayBuffer(_buf_cfg(buffer_size=8))
    for i in range(8):
        buf.add(np.ones(4) * i, 0, 0.0, np.zeros(4), 0.0, np.ones(3))
    stamps = buf.write_seq[np.array([0, 1])].copy()
    buf.add(np.ones(4) * 99, 0, 0.0, np.zeros(4), 0.0, np.ones(3))
    buf.update_priorities(np.array([0, 1]), np.array([5.0, 5.0]),
                          expect_seq=stamps)
    assert buf.prio[0] == pytest.approx(1.0)       # slot 0 overwritten
    assert buf.prio[1] == pytest.approx(5.0 + 1e-3)
    assert buf.max_prio == pytest.approx(5.0 + 1e-3)


def test_prioritized_batched_training_completes():
    cfg = rl.RouterConfig(variant="guided", n_instances=2,
                          explore_episodes=2, seed=0)
    bcfg = batched_rl.BatchedRLConfig(n_envs=2, m_max=2,
                                      prioritized=True,
                                      learn_batch_size=32)
    out = batched_rl.train_batched(
        cfg, lambda ep: wl.Scenario.homogeneous(
            PROF, 2, wl.to_requests(wl.generate(30, seed=ep), rate=8.0,
                                    seed=ep + 9)),
        3, bcfg=bcfg)
    agent = out["agent"]
    assert agent.cfg.prioritized
    assert all(h["n"] == 30 for h in out["history"])
    assert agent.steps > 0                 # learner actually ran
    agent._resolve_priorities()
    live = agent.buffer.prio[:agent.buffer.size]
    assert len(np.unique(np.round(live, 9))) > 1   # TD priorities applied


# -- trend gate ---------------------------------------------------------------

def _report(ok=True, seconds=10.0, acc=0.9, p95=50.0):
    return {"results": [{
        "bench": "demo", "ok": ok, "seconds": seconds,
        "rows": [{"name": "demo_row", "us_per_call": "1.0",
                  "derived": f"acc={acc} p95_e2e={p95} n=100"}],
    }], "failures": []}


def test_trend_gate_passes_within_band_and_fails_on_regression():
    from benchmarks.trend import compare
    base = _report()
    ok, _ = compare(_report(acc=0.88, p95=55.0, seconds=20.0), base)
    assert ok == []
    bad_acc, _ = compare(_report(acc=0.4), base)
    assert any("acc" in r for r in bad_acc)
    # fraction-scale metrics gate at the tighter frac_tol band: a 0.2
    # accuracy drop fails even though it is within the generic 35% tol
    bad_frac, _ = compare(_report(acc=0.7), base)
    assert any("acc" in r for r in bad_frac)
    bad_p95, _ = compare(_report(p95=90.0), base)
    assert any("p95_e2e" in r for r in bad_p95)
    bad_time, _ = compare(_report(seconds=100.0), base)
    assert any("wall time" in r for r in bad_time)
    bad_fail, _ = compare(_report(ok=False), base)
    assert any("FAILED" in r for r in bad_fail)
    # unknown keys (n=) and new rows never gate
    cur = _report()
    cur["results"][0]["rows"][0]["derived"] += " n=5"
    cur["results"][0]["rows"].append(
        {"name": "new_row", "us_per_call": "1.0", "derived": "acc=0.1"})
    ok, notes = compare(cur, base)
    assert ok == [] and any("new" in n for n in notes)


def test_trend_gate_infers_direction_for_bare_value_rows():
    from benchmarks.trend import compare
    base = {"results": [{"bench": "table1", "ok": True, "seconds": 5.0,
                         "rows": [{"name": "table1_hint_acc",
                                   "us_per_call": "1",
                                   "derived": "0.766"}]}]}
    import copy
    cur = copy.deepcopy(base)
    cur["results"][0]["rows"][0]["derived"] = "0.30"
    bad, _ = compare(cur, base)
    assert any("table1_hint_acc" in r for r in bad)
    assert compare(base, base)[0] == []


def test_trend_gate_per_entry_directions():
    """A bench's baseline entry can carry its own ``directions`` map
    (emit_direction -> run.py --json); it beats the global prefix
    lists, so new keys gate the way the bench declared."""
    from benchmarks.trend import compare

    def rep(v, dirs):
        return {"results": [{
            "bench": "jaxsim", "ok": True, "seconds": 5.0,
            "directions": dirs,
            "rows": [{"name": "jaxsim_trainer", "us_per_call": "1",
                      "derived": f"episodes_per_sec_vec={v} "
                                 f"eps_gap={v}"}]}]}
    # prefix match: episodes_per_sec_* declared higher-is-better
    dirs = {"episodes_per_sec": "high", "eps_gap": "low"}
    bad, _ = compare(rep(10.0, dirs), rep(40.0, dirs))
    assert any("episodes_per_sec_vec" in r for r in bad)
    assert compare(rep(45.0, dirs), rep(40.0, dirs))[0] == []
    # exact-key override: the global lists call ``eps*`` higher-is-
    # better, the entry says lower -- the entry wins
    bad, _ = compare(rep(9.0, dirs), rep(5.0, dirs))
    assert any("eps_gap" in r for r in bad)
    ok, _ = compare(rep(4.0, {"eps_gap": "low"}),
                    rep(5.0, {"eps_gap": "low"}))
    assert ok == []


def test_trend_gate_flags_missing_rows():
    from benchmarks.trend import compare
    cur = _report()
    cur["results"][0]["rows"] = []
    bad, _ = compare(cur, _report())
    assert any("missing" in r for r in bad)


# -- per-tenant quotas / weighted-fair shedding -------------------------------

def _burst_stream(n_bursts=30, flood=14, queue_align=0.0145):
    """Adversarial timing: tenant 'flood' fills the bounded queue in
    bursts; tenant 'light' always arrives in the same tick, after the
    flood -- the worst case for tenant-blind shedding."""
    from repro.serving.request import Request
    reqs = []
    for burst in range(n_bursts):
        t = burst * 2.0
        for i in range(flood):
            reqs.append(Request(prompt_tokens=300, decode_tokens=400,
                                arrival=t + i * 0.001, tenant="flood"))
        reqs.append(Request(prompt_tokens=80, decode_tokens=60,
                            arrival=t + queue_align, tenant="light"))
    return reqs


def _run_quota_gateway(weights, backend="py"):
    gw = Gateway(GatewayConfig(queue_cap=8, on_full="shed",
                               tenant_weights=weights, backend=backend),
                 (PROF,) * 2, make_gateway_policy("rr"))
    stats = gw.run(_burst_stream())
    return gw, stats


def test_weighted_fair_shed_protects_under_share_tenant():
    """Blind shedding punishes whoever arrives at saturation (here: the
    light tenant, 100% shed); weighted-fair eviction sheds the tenant
    most over its queue share instead."""
    _, blind = _run_quota_gateway(None)
    _, fair = _run_quota_gateway({"flood": 1.0, "light": 1.0})
    b_light = blind["snapshot"]["tenants"]["light"]
    f_light = fair["snapshot"]["tenants"]["light"]
    assert b_light["admitted"] == 0          # blind: always shed
    assert f_light["shed"] == 0              # fair: fully protected
    assert fair["snapshot"]["shed_fairness"] \
        > blind["snapshot"]["shed_fairness"]
    # the shed burden moved onto the over-share tenant
    assert f_light["shed_burden"] == 0.0
    assert fair["snapshot"]["tenants"]["flood"]["shed_burden"] > 1.0
    # books balance under eviction accounting (offered counted once)
    n = len(_burst_stream())
    for stats in (blind, fair):
        assert stats["admitted"] + stats["shed"] == n
        snap = stats["snapshot"]
        assert sum(d["shed"] for d in snap["tenants"].values()) \
            == stats["shed"]
        assert sum(d["admitted"] for d in snap["tenants"].values()) \
            == stats["admitted"]


def test_fair_shed_respects_weights():
    """A zero-weight tenant is entitled to nothing: it gets no
    protection (its own arrivals shed at saturation) and never evicts
    the weighted tenant."""
    _, fair = _run_quota_gateway({"flood": 1.0, "light": 0.0})
    light = fair["snapshot"]["tenants"]["light"]
    assert light["admitted"] == 0


def test_fair_shed_on_vec_backend_matches_py():
    _, py = _run_quota_gateway({"flood": 1.0, "light": 1.0},
                               backend="py")
    gw_vec, vec = _run_quota_gateway({"flood": 1.0, "light": 1.0},
                                     backend="vec")
    assert vec["shed"] == py["shed"]
    assert vec["admitted"] == py["admitted"]
    assert vec["snapshot"]["shed_fairness"] == pytest.approx(
        py["snapshot"]["shed_fairness"])
    from repro.serving.request import Phase
    for r in gw_vec.shed:            # evicted requests stay SHED after
        assert r.phase is Phase.SHED  # the end-of-run arena sync


def test_no_weights_preserves_blind_behaviour():
    """tenant_weights=None must reproduce the pre-quota gateway
    decision for decision (no eviction machinery in the path)."""
    scn_a, scn_b = _scenario(seed=9, rate=40.0), _scenario(seed=9,
                                                           rate=40.0)
    gw_a = Gateway(GatewayConfig(queue_cap=4, on_full="shed"),
                   (PROF,) * 2, make_gateway_policy("rr"))
    gw_b = Gateway(GatewayConfig(queue_cap=4, on_full="shed",
                                 tenant_weights=None),
                   (PROF,) * 2, make_gateway_policy("rr"))
    a, b = gw_a.run(scn_a), gw_b.run(scn_b)
    assert a["shed"] == b["shed"] and a["admitted"] == b["admitted"]


def test_shed_fairness_index_bounds_and_none():
    m = StreamMetrics()
    assert m.shed_fairness() is None         # no tenants yet
    m.on_admit("a")
    m.on_admit("b")
    assert m.shed_fairness() == pytest.approx(1.0)
    for _ in range(9):
        m.on_shed("b")
    fairness = m.shed_fairness()
    assert 0.0 < fairness < 1.0
    snap = m.snapshot(0.0)
    assert snap["shed_fairness"] == pytest.approx(fairness)
    assert snap["tenants"]["a"]["shed_burden"] == 0.0
    assert snap["tenants"]["b"]["shed_burden"] > 1.0


def test_fair_evict_in_defer_mode_is_lossless():
    """Defer mode must never lose a request, with or without fair
    eviction: a displaced victim returns to the client overflow and is
    re-admitted when the queue drains."""
    reqs = _burst_stream()
    gw = Gateway(GatewayConfig(queue_cap=8, on_full="defer",
                               tenant_weights={"flood": 1.0,
                                               "light": 1.0}),
                 (PROF,) * 2, make_gateway_policy("rr"))
    stats = gw.run(reqs)
    assert stats["shed"] == 0
    assert stats["admitted"] == len(reqs)
    assert stats["n"] == len(reqs)          # all served
    # metrics admit-reversal kept offered counts exact
    snap = stats["snapshot"]
    assert snap["admitted"] == len(reqs)
    # queue-occupancy bookkeeping fully drained (keys pruned at zero)
    assert gw._q_tenant == {}
