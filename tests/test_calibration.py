"""Calibration fit machinery, profile JSON round-trip, the sim-vs-engine
fidelity harness, and the calibrated-profile wiring."""
import dataclasses

import numpy as np
import pytest

from repro.core import calibrate as cal
from repro.core.policies import make_policy
from repro.core.profiles import (A100_LLAMA31_8B, V100_LLAMA2_7B,
                                 HardwareProfile, profile_from_json,
                                 profile_to_json)
from repro.core.simulator import Cluster, run_heuristic
from repro.core.workload import generate, make_scenario, to_requests
from repro.serving import fidelity as fid

GROUND_TRUTH = HardwareProfile(
    name="stub-gpu", grad1=2.4e-4, grad2=1.9e-5, t_decode_base=0.011,
    t_prefill_base=3.0e-4, capacity_tokens=5_000)


def _stub_engine_samples(profile, noise=0.0, seed=0):
    """What the sweep would measure on an engine whose true cost model
    IS ``profile``: prefill t(p) = tpre + grad1*p, decode t(R) = tdec +
    grad2*R, with optional relative timing noise."""
    rng = np.random.default_rng(seed)
    ccfg = cal.CalibrationConfig()

    def jitter():
        return 1.0 + noise * rng.standard_normal()
    pre = [(float(p),
            (profile.t_prefill_base + profile.grad1 * p) * jitter())
           for p in ccfg.prompt_grid]
    dec = [(float(b * c),
            (profile.t_decode_base + profile.grad2 * b * c) * jitter())
           for b, c in ccfg.decode_grid]
    return pre, dec


def test_fit_roundtrip_recovers_ground_truth():
    """Synthetic ground-truth profile -> timed engine stub -> the fit
    must recover grad1/grad2 within tolerance, with the diagnostics the
    CI calibration gate asserts (R^2 >= 0.95, grad1 > grad2 > 0)."""
    pre, dec = _stub_engine_samples(GROUND_TRUTH, noise=0.01)
    res = cal.fit_calibration(pre, dec, base=GROUND_TRUTH, name="refit")
    assert res.profile.grad1 == pytest.approx(GROUND_TRUTH.grad1,
                                              rel=0.05)
    assert res.profile.grad2 == pytest.approx(GROUND_TRUTH.grad2,
                                              rel=0.05)
    assert res.profile.t_decode_base == pytest.approx(
        GROUND_TRUTH.t_decode_base, rel=0.10)
    assert res.prefill_fit.r2 >= 0.95
    assert res.decode_fit.r2 >= 0.95
    assert res.ok
    # thresholds are inherited from the base, not fitted
    assert res.profile.capacity_tokens == GROUND_TRUTH.capacity_tokens


def test_fit_is_exact_on_noiseless_samples():
    pre, dec = _stub_engine_samples(GROUND_TRUTH, noise=0.0)
    res = cal.fit_calibration(pre, dec, base=GROUND_TRUTH)
    assert res.prefill_fit.r2 == pytest.approx(1.0, abs=1e-9)
    assert res.decode_fit.r2 == pytest.approx(1.0, abs=1e-9)
    assert res.prefill_fit.residual_band == pytest.approx(0.0, abs=1e-12)
    assert res.profile.grad1 == pytest.approx(GROUND_TRUTH.grad1,
                                              rel=1e-6)
    assert res.profile.t_prefill_base == pytest.approx(
        GROUND_TRUTH.t_prefill_base, rel=1e-6)


def test_calibration_sanity_flags_inverted_gradients():
    """A 'calibration' where decode interference outprices prefill work
    must be flagged, not silently shipped."""
    inverted = dataclasses.replace(GROUND_TRUTH, grad1=1e-6, grad2=1e-4)
    pre, dec = _stub_engine_samples(inverted)
    res = cal.fit_calibration(pre, dec, base=inverted)
    assert not res.ok


def test_profile_json_roundtrip(tmp_path):
    prof = dataclasses.replace(GROUND_TRUTH, name="artifact")
    assert profile_from_json(profile_to_json(prof)) == prof
    # unknown keys from newer writers are ignored
    d = profile_to_json(prof)
    d["diagnostic_only_field"] = 42
    assert profile_from_json(d) == prof
    # the full CalibrationResult artifact round-trips too
    pre, dec = _stub_engine_samples(prof)
    res = cal.fit_calibration(pre, dec, base=prof, name="artifact")
    path = tmp_path / "profile.json"
    res.save(str(path))
    assert cal.load_profile(str(path)) == res.profile
    import json
    res2 = cal.CalibrationResult.from_json(json.loads(path.read_text()))
    assert res2.profile == res.profile
    assert res2.decode_fit == res.decode_fit


def test_prefill_base_enters_iteration_time_and_vec_parity():
    """t_prefill_base must price prefilling iterations (and only them)
    identically on the scalar profile, the py stepper, and vecsim."""
    prof = dataclasses.replace(V100_LLAMA2_7B, t_prefill_base=0.004)
    assert prof.iteration_time(0, 100) == pytest.approx(
        prof.t_decode_base + prof.grad2 * 100)
    assert prof.iteration_time(50, 100) == pytest.approx(
        prof.t_decode_base + prof.grad1 * 50 + prof.grad2 * 100
        + 0.004)
    ra = to_requests(generate(80, seed=3), rate=20.0, seed=4)
    rb = to_requests(generate(80, seed=3), rate=20.0, seed=4)
    sa = run_heuristic(Cluster(prof, 3), ra,
                       make_policy("round_robin", prof))
    sb = run_heuristic(Cluster(prof, 3, backend="vec"), rb,
                       make_policy("round_robin", prof))
    for a, b in zip(ra, rb):
        assert a.finished == b.finished
        assert a.first_token == b.first_token
    assert sa["e2e_mean"] == sb["e2e_mean"]
    # and the base actually costs time vs the zero-base profile
    rc = to_requests(generate(80, seed=3), rate=20.0, seed=4)
    sc = run_heuristic(Cluster(V100_LLAMA2_7B, 3), rc,
                       make_policy("round_robin", V100_LLAMA2_7B))
    assert sa["e2e_mean"] > sc["e2e_mean"]


def test_make_scenario_profiles_override():
    calibrated = dataclasses.replace(GROUND_TRUTH, name="cal-a")
    mix = (calibrated, V100_LLAMA2_7B, A100_LLAMA31_8B)
    scn = make_scenario(seed=5, profiles=mix, n_requests=50)
    assert scn.profiles == mix
    assert scn.m == 3
    cap = min(p.capacity_tokens for p in mix)
    for r in scn.requests:
        assert r.prompt_tokens + r.decode_tokens <= cap
    # same seed, sampled shape: the override really changed the cluster
    sampled = make_scenario(seed=5, n_requests=50)
    assert sampled.profiles != scn.profiles


# -- fidelity harness --------------------------------------------------------

def test_fidelity_simulator_backends_match_bitwise():
    """The harness's vec-vs-py deltas must be exactly zero and the
    report must carry the full percentile/delta shape."""
    fcfg = fid.FidelityConfig(backends=("py", "vec"), n_requests=30)
    rep = fid.run_fidelity(V100_LLAMA2_7B, fcfg)
    assert set(rep["backends"]) == {"py", "vec"}
    assert rep["backends"]["py"]["completed"] == 30
    assert rep["backends"]["vec"] == rep["backends"]["py"]
    d = rep["deltas"]["vec_vs_py"]
    for metric in fid.METRICS:
        assert set(d[metric]) == {"p50", "p95", "p99"}
        for pct in d[metric].values():
            assert pct["abs"] == 0.0
            assert pct["rel"] == 0.0
    # the serving profile is engine-sized
    assert rep["profile"]["capacity_tokens"] <= fcfg.capacity_tokens


def test_fidelity_stream_is_deterministic():
    fcfg = fid.FidelityConfig(n_requests=12)
    assert fid.make_stream(fcfg) == fid.make_stream(fcfg)
    for p, d, _ in fid.make_stream(fcfg):
        assert p in fcfg.prompt_lengths
        assert fcfg.decode_range[0] <= d <= fcfg.decode_range[1]


def test_fidelity_engine_backend_smoke():
    """Real-engine leg on a tiny config: the engine serves the whole
    stream and its percentile deltas against the simulator are finite
    and small on the virtual clock."""
    import jax
    from repro.configs import get_config
    from repro.models import params as params_lib
    model_cfg = get_config("qwen3-0.6b").reduced()
    params = params_lib.init_params(jax.random.PRNGKey(0), model_cfg)
    fcfg = fid.FidelityConfig(
        backends=("py", "engine"), n_requests=12, n_instances=1,
        n_slots=2, cache_len=64, capacity_tokens=200,
        prompt_lengths=(16, 32), decode_range=(4, 12), rate=6.0)
    rep = fid.run_fidelity(V100_LLAMA2_7B, fcfg, model_cfg=model_cfg,
                           params=params)
    assert rep["backends"]["engine"]["completed"] == 12
    rel = rep["deltas"]["engine_vs_py"]["e2e"]["p95"]["rel"]
    assert rel is not None and abs(rel) < 0.5
