"""The readers of the program's own spans and stamps: ``step_host_ms``,
``fetch_idle_share`` and ``queue_wait_p90_ms`` on hand-made inputs, on
a program without the spans (which reads nothing), in a CPU rehearsal
of the steady cell with ``--trace 1``, and on a short trace recorded on
a TPU v5e (``data/v5e_trace_spans.json.gz``)."""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

from bench_chip_paths import BENCHMARK, load

devtrace = load("devtrace")
run = load("run")
spec = load("spec")

SEED = 2**31 + 977
STEADY = "qwen3-0.6b.paper-mix"
CELLS = ("qwen3-0.6b.paper-mix", "qwen3-0.6b.paper-mix-saturated",
         "qwen3-0.6b-x4.paper-mix-saturated")


def _readers(cell=STEADY):
    return {m.name: m.read for m in spec.load_cell(BENCHMARK, cell).per_layer}


def _toy():
    host = [("advance", 0, 100),
            ("engine.step", 5, 45), ("engine.decode", 10, 44),
            ("engine.fetch", 20, 35),
            ("engine.step", 50, 95), ("engine.prefill", 52, 60),
            ("engine.fetch", 55, 58), ("engine.decode", 62, 94),
            ("engine.fetch", 70, 90),
            ("engine.step", 98, 110)]           # ends past the window
    ops = {"TPU:0": [("%fusion.1", 0, 20), ("%fusion.2", 36, 56),
                     ("%fusion.3", 80, 100)],
           "TPU:1": [("%fusion.1", 0, 100)]}
    return devtrace.Trace(ops=ops, host=host, window=(0, 100))


def test_the_cells_report_the_span_metrics():
    for cell in CELLS:
        names = set(_readers(cell))
        assert {"step_host_ms", "fetch_idle_share"} <= names
        assert ("queue_wait_p90_ms" in names) == (cell == STEADY)


def test_step_host_ms_is_the_steps_self_time():
    ctx = SimpleNamespace(trace=_toy(), devices=["TPU:0"])
    # (40 - 15) and (45 - 3 - 20) ns, the step past the window left out
    assert _readers()["step_host_ms"](ctx) == pytest.approx(23.5e-6)


def test_fetch_idle_share_is_idle_time_inside_fetches():
    readers = _readers()
    tr = _toy()
    one = SimpleNamespace(trace=tr, devices=["TPU:0"])
    # gaps (20, 36) and (56, 80) meet the fetches for 15 + 2 + 10 ns
    assert readers["fetch_idle_share"](one) == pytest.approx(27.0)
    assert readers["idle_share"](one) == pytest.approx(40.0)
    both = SimpleNamespace(trace=tr, devices=["TPU:0", "TPU:1"])
    assert readers["fetch_idle_share"](both) == pytest.approx(13.5)
    assert readers["fetch_idle_share"](both) <= readers["idle_share"](both)


def test_queue_wait_p90_ms_reads_the_slots_given_in_the_window():
    req = lambda q, s: SimpleNamespace(wall_queued=q,     # noqa: E731
                                       wall_started=s)
    inside = {i: req(10.0, 10.0 + 1e-3 * (i + 1)) for i in range(10)}
    others = {20: req(5.0, 25.0),                 # started after it
              21: req(12.0, None),                # still queued
              22: SimpleNamespace()}              # no stamps at all
    rec = SimpleNamespace(t_open=10.0, t_close=20.0,
                          requests={**inside, **others})
    # waits 1..10 ms: numpy's linear 90th percentile
    assert _readers()["queue_wait_p90_ms"](SimpleNamespace(rec=rec)) == \
        pytest.approx(9.1)


def test_a_program_without_spans_reads_nothing():
    readers = _readers()
    tr = _toy()
    tr.host = [s for s in tr.host if not s[0].startswith("engine.")]
    ctx = SimpleNamespace(trace=tr, devices=["TPU:0"], rec=SimpleNamespace(
        t_open=0.0, t_close=1.0, requests={1: SimpleNamespace()}))
    for name in ("step_host_ms", "fetch_idle_share", "queue_wait_p90_ms"):
        assert readers[name](ctx) is None, name


def test_rehearsal_traced_run_reads_the_program_spans():
    args = run.parse(["--workload", STEADY, "--seed", str(SEED),
                      "--seconds", "6", "--trace", "1"])
    result = run.run(args, rehearse=True)
    metrics = result["metrics"]
    for name in ("queue_wait_p90_ms", "step_host_ms"):
        assert isinstance(metrics[name]["value"], float), name
        assert metrics[name]["value"] > 0
    assert metrics["step_host_ms"]["unit"] == "ms"
    # the CPU runs no device operation, so no device share is read
    assert "fetch_idle_share" not in metrics and "idle_share" not in metrics
    assert result["correct"] is True


# A short trace recorded on a TPU v5e by ``record_trace.py``'s ``keep``:
# 2.9 s (the first 40 traced advances) of the first chip of the
# ``qwen3-0.6b-x4.paper-mix-saturated`` cell, with the program's spans.
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_trace_spans.json.gz")
PROGRAM = ("engine.", "cluster.", "gateway.")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    return devtrace.Trace.from_json(d["trace"]), \
        {tuple(k): v for k, v in d["steps"]}


def test_recorded_spans_trace_readers(recorded):
    tr, _ = recorded
    readers = _readers("qwen3-0.6b-x4.paper-mix-saturated")
    ctx = SimpleNamespace(trace=tr, devices=list(tr.ops))
    host_ms = readers["step_host_ms"](ctx)
    assert 0.1 < host_ms < 10
    fetch_idle = readers["fetch_idle_share"](ctx)
    # one of four replicas: the chip waits while the host fetches from
    # the other three
    assert 50 < fetch_idle <= readers["idle_share"](ctx) < 100


def test_recorded_idle_gaps_lie_in_program_spans(recorded):
    tr, _ = recorded
    dev = next(iter(tr.ops))
    gaps = [g for g in devtrace.idle_gaps(tr, dev) if g[1] - g[0] >= 1e6]
    assert gaps
    for s, e in gaps:
        mid = (s + e) / 2
        assert any(a <= mid < b for n, a, b in tr.host
                   if n.startswith(PROGRAM)), (s, e)


def test_recorded_steps_keep_their_names(recorded):
    tr, steps = recorded
    dev = next(iter(tr.ops))
    names = {n.split("(")[0] for n, _, _ in tr.modules[dev]}
    assert "jit_decode_step" in names and "jit__lambda" not in names
    # the decode kernel keeps the name the readers find it by
    decode = devtrace.programs(tr, dev, "decode")
    assert len(decode) == len(steps) > 0
    assert {n.split("(")[0] for n, _, _ in decode} == {"jit_decode_step"}
