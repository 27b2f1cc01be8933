"""Wall-clock spans and request stamps on the served path: a reduced
engine fleet served through ``Gateway.run`` under ``jax.profiler``
writes every span of the served path on the profiler's host plane,
nested as the layers are; the spans change no served id; and the wall
stamps are set on admission and cleared wherever a request loses its
slot."""
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import V100_LLAMA2_7B
from repro.launch.serve import build_engine_fleet
from repro.models import params as params_lib
from repro.serving.engine import LLMInstance
from repro.serving.gateway import EngineClusterAdapter, Gateway, \
    GatewayConfig
from repro.serving.policies import make_gateway_policy
from repro.serving.request import Request
from repro.serving.scheduler import FCFS

CFG = get_config("qwen3-0.6b").reduced()
SPANS = {"gateway.tick": set(), "gateway.route": {"rid"},
         "cluster.advance": set(), "engine.step": {"replica", "rows"},
         "engine.prefill": {"rid", "prompt"}, "engine.decode": set(),
         "engine.fetch": set()}


def _stream(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt_tokens=int(p), decode_tokens=int(d),
                    arrival=0.03 * i,
                    tokens=rng.integers(0, CFG.vocab_size, p).tolist())
            for i, (p, d) in enumerate(zip(rng.integers(4, 24, n),
                                           rng.integers(2, 9, n)))]


def _serve(reqs, profile_dir=None):
    engines = build_engine_fleet("qwen3-0.6b", 2, reduced=True, n_slots=2,
                                 cache_len=64, seed=0)
    gw = Gateway(GatewayConfig(), None, make_gateway_policy("mixing"),
                 cluster=EngineClusterAdapter(engines))
    if profile_dir is None:
        gw.run(reqs)
        return None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(profile_dir, profiler_options=opts):
        gw.run(reqs)
    path = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)[0]
    host = next(p for p in jax.profiler.ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    return [(line.name, e.name, e.start_ns, e.end_ns,
             {k for k, _ in e.stats})
            for line in host.lines for e in line.events]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    reqs = _stream()
    events = _serve(reqs, str(tmp_path_factory.mktemp("profile")))
    return reqs, events


def _inside(inner, outer):
    return inner[0] == outer[0] and outer[2] <= inner[2] \
        and inner[3] <= outer[3]


def test_served_path_writes_every_span(traced):
    reqs, events = traced
    assert all(r.finished is not None for r in reqs)
    ours = [e for e in events if e[1] in SPANS]
    assert {e[1] for e in ours} == set(SPANS)
    for _, name, _, _, args in ours:
        assert args == SPANS[name], name
    # the names the benchmark's window is taken from stay the harness's
    assert not any(e[1] in ("advance", "route") for e in events)
    by = lambda name: [e for e in ours if e[1] == name]   # noqa: E731
    for inner, outer in (("engine.fetch", "engine.decode"),
                         ("engine.fetch", "engine.step"),
                         ("engine.prefill", "engine.step"),
                         ("engine.decode", "engine.step"),
                         ("engine.step", "cluster.advance"),
                         ("cluster.advance", "gateway.tick"),
                         ("gateway.route", "gateway.tick")):
        for e in by(inner):
            assert any(_inside(e, o) for o in by(outer)), (inner, outer)
    # one prefill per request, which fetches nothing: the one fetch of
    # each decode pass brings the first tokens with the carried ids
    assert len(by("engine.prefill")) == len(reqs)
    assert len(by("engine.fetch")) == len(by("engine.decode"))
    for e in by("engine.fetch"):
        assert not any(_inside(e, p) for p in by("engine.prefill"))


def test_spans_change_no_served_id(traced):
    reqs, _ = traced
    plain = _stream()
    _serve(plain)
    assert [r.output_tokens for r in plain] == \
        [r.output_tokens for r in reqs]


def test_wall_stamps_are_set_in_order(traced):
    reqs, _ = traced
    for r in reqs:
        assert r.wall_queued is not None and r.wall_started is not None
        assert r.wall_queued <= r.wall_started


@pytest.fixture(scope="module")
def params():
    return params_lib.init_params(jax.random.PRNGKey(0), CFG)


def _engine(params):
    return LLMInstance(CFG, params, V100_LLAMA2_7B, FCFS(), n_slots=2,
                       cache_len=64)


def _started(eng, n=2):
    reqs = _stream(n, seed=1)
    for r in reqs:
        r.decode_tokens = 20
        r.wall_queued = 1.0
        eng.submit(r)
    for _ in range(n):
        eng.step()
    for r in reqs:
        assert r.wall_started is not None and r.wall_started > 1.0
    return reqs


def test_preemption_clears_the_start_stamp(params):
    eng = _engine(params)
    reqs = _started(eng)
    newest = max(reqs, key=lambda r: r.prefill_done)
    eng._preempt_newest()
    assert newest.wall_started is None and newest.wall_queued == 1.0
    eng.step()
    assert newest.wall_started is not None


def test_fail_clears_the_start_stamps(params):
    eng = _engine(params)
    reqs = _started(eng)
    orphans = eng.fail()
    assert set(map(id, orphans)) == set(map(id, reqs))
    for r in reqs:
        assert r.wall_started is None and r.wall_queued == 1.0


def test_steal_clears_the_start_stamp(params):
    eng = _engine(params)
    reqs = _started(eng)
    assert eng.steal(reqs[0])
    assert reqs[0].wall_started is None and reqs[0].wall_queued == 1.0
    assert reqs[1].wall_started is not None


def test_gateway_stamps_the_queue_on_overflow_drain():
    """A deferred request is stamped when it finally enters the
    cluster's queue, not when it arrived."""
    engines = build_engine_fleet("qwen3-0.6b", 1, reduced=True, n_slots=2,
                                 cache_len=64, seed=0)
    gw = Gateway(GatewayConfig(queue_cap=1, on_full="defer"), None,
                 make_gateway_policy("mixing"),
                 cluster=EngineClusterAdapter(engines))
    reqs = _stream(6)
    for r in reqs:
        r.arrival = 0.0
    gw.run(reqs)
    assert all(r.finished is not None for r in reqs)
    for r in reqs:
        assert r.wall_queued <= r.wall_started
    # the deferred ones entered the queue after the first was started
    first = min(reqs, key=lambda r: r.wall_started)
    later = [r for r in reqs if r.wall_queued > first.wall_started]
    assert later
