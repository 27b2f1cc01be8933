"""CPU rehearsal of a whole benchmark run: the cell's real traffic
through the gateway and engine replicas at the program's small preset,
past the look for a chip.  Checks the shape of the result line and that
a seed fixes the routing decisions and the batch of every engine step
(on the saturated mix, whose short rehearsal routes the most requests).
"""
import json

import pytest

from bench_chip_paths import BENCHMARK, load

run = load("run")
SEED = 2**31 + 4321
CELL = "qwen3-0.6b.paper-mix-saturated"
STEADY = "qwen3-0.6b.paper-mix"


def _run(seconds, trace=0, seed=SEED, cell=CELL):
    recs = []

    class Kept(run.replay.Recorder):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            recs.append(self)
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run.replay, "Recorder", Kept)
        result = run.run(args, rehearse=True)
    return result, recs[0]


def _schedule(rec):
    """Routing decisions and per-step batches, in stream order ids."""
    order = {rid: i for i, rid in enumerate(sorted(rec.requests))}
    routes = [(order[rid], rec.requests[rid].instance)
              for rid in rec.routed]
    steps = {}
    for rid, req in rec.requests.items():
        for t in req.token_times:
            steps.setdefault((req.instance, t), set()).add(order[rid])
    return routes, steps


@pytest.fixture(scope="module")
def two_runs():
    return _run(10.0), _run(11.0)


def _check_shape(result, cell):
    bench = json.load(open(BENCHMARK))
    keys = list(result)
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in keys
    assert keys[-1] == "checks"
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
        assert c["value"] <= c["limit"]
    assert result["info"]["window_compiles"] == 0


def test_result_line_shape(two_runs):
    (result, _), _ = two_runs
    _check_shape(result, CELL)


def test_steady_cell_result_line_shape():
    result, rec = _run(6.0, cell=STEADY)
    _check_shape(result, STEADY)
    assert "e2e_p90_ms" in result["metrics"]
    assert rec.completed_in_window()


def test_main_prints_the_checks_last(monkeypatch, capsys):
    monkeypatch.setattr(run, "run", lambda args: {
        "correct": True, "attempted": 1, "failed": 0, "metrics": {},
        "device": {}, "info": {"x": 1},
        "checks": {"logit_gap": {"value": 0.5, "limit": 1.0}}})
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds",
                     "1"]) == 0
    out, err = capsys.readouterr()
    assert err.strip().splitlines()[-1] == "check logit_gap 0.5 limit 1.0"
    assert list(json.loads(out.strip().splitlines()[-1]))[-1] == "checks"


def test_seed_fixes_routes_and_batches(two_runs):
    (_, rec_a), (_, rec_b) = two_runs
    routes_a, steps_a = _schedule(rec_a)
    routes_b, steps_b = _schedule(rec_b)
    n = min(len(routes_a), len(routes_b))
    assert n > 30
    assert routes_a[:n] == routes_b[:n]
    horizon = min(rec_a.adv_t[-2], rec_b.adv_t[-2])
    common_a = {k: v for k, v in steps_a.items() if k[1] <= horizon}
    common_b = {k: v for k, v in steps_b.items() if k[1] <= horizon}
    assert len(common_a) > 100
    assert common_a == common_b


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err
