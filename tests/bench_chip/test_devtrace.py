"""The reduction from a device trace to per-layer metrics: interval
arithmetic on a hand-made trace, and every device-trace reader on a
short trace recorded on a TPU v5e and committed here
(``data/v5e_trace.json.gz``: 2.8 s of the first chip of the
``qwen3-0.6b-x4.paper-mix-saturated`` cell, with the decode steps it
ran, as ``record_trace.py`` writes it)."""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

from bench_chip_paths import BENCHMARK, load

devtrace = load("devtrace")
spec = load("spec")

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_trace.json.gz")


def _toy():
    ops = {"TPU:0": [
        ("%fusion.1 = bf16[16] fusion(%p.1)", 0, 10),
        ("%decode_attention.4 = bf16[16,8,2,128] custom-call(%a.1)", 12, 20),
        ("%fusion.2 = bf16[16] fusion(%decode_attention.4)", 18, 30),
        ("%flash_attention.2 = bf16[1,128,16,128] custom-call(%q.3)", 60,
         70)]}
    modules = {"TPU:0": [("jit__lambda_", 0, 30), ("jit__lambda_", 55, 75),
                         ("jit_insert", 80, 90)]}
    host = [("advance", 0, 100), ("PjitFunction(_lambda)", 40, 58),
            ("route", 100, 105)]
    return devtrace.Trace(ops=ops, modules=modules, host=host,
                          window=(0, 100))


def test_union_busy_and_gaps():
    tr = _toy()
    assert devtrace.union(tr.ops["TPU:0"], 0, 100) == [
        (0, 10), (12, 30), (60, 70)]
    assert devtrace.busy_ns(tr, "TPU:0") == 10 + 18 + 10
    assert devtrace.idle_gaps(tr, "TPU:0") == [
        (10, 12), (30, 60), (70, 100)]
    # a window clips the intervals
    tr.window = (5, 65)
    assert devtrace.busy_ns(tr, "TPU:0") == 5 + 18 + 5


def test_kernels_are_known_by_their_instruction_name():
    tr = _toy()
    kinds = [devtrace.kernel_kind(n) for n, _, _ in tr.ops["TPU:0"]]
    assert kinds == [None, "decode", None, "prefill"]


def test_short_names():
    assert devtrace.short_name(
        "%copy.86 = bf16[28,16,2048]{2,1,0:T(8,128)} copy(bf16[28,16,2048]"
        "{2,1,0} %get-tuple-element.399)") == "%copy.86 copy bf16[28,16,2048]"
    assert devtrace.short_name(
        "%while.1 = (s32[]{:T(128)}, bf16[16,1,1024]{2,0,1}) while(%t.2), "
        "body=%b") == "%while.1 while (tuple)"
    assert devtrace.short_name("jit__lambda(123)") == "jit__lambda(123)"


def test_programs_are_told_apart_by_their_kernel():
    tr = _toy()
    assert devtrace.programs(tr, "TPU:0", "decode") == [
        ("jit__lambda_", 0, 30)]
    assert devtrace.programs(tr, "TPU:0", "prefill") == [
        ("jit__lambda_", 55, 75)]
    assert devtrace.program_seconds(tr, ["TPU:0"], "decode") == \
        pytest.approx(30e-9)
    assert devtrace.kernel_seconds(tr, ["TPU:0"], "decode") == \
        pytest.approx(8e-9)


def test_gaps_are_blamed_on_host_spans():
    tr = _toy()
    assert devtrace.host_span_at(tr, 45) == \
        "advance/PjitFunction(_lambda)"
    assert devtrace.host_span_at(tr, 20) == "advance"
    assert devtrace.host_span_at(tr, 102) == "route"
    assert devtrace.host_span_at(tr, 200) == "gateway"


def test_json_round_trip():
    tr = _toy()
    back = devtrace.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back == tr


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    tr = devtrace.Trace.from_json(d["trace"])
    steps = {tuple(k): v for k, v in d["steps"]}
    cell = spec.load_cell(BENCHMARK, "qwen3-0.6b-x4.paper-mix-saturated")
    return tr, steps, cell


DEVICE_READERS = ("decode.mfu", "decode.hbm_share", "decode_attn_roofline",
                  "idle_share")


def test_recorded_trace_has_decode_and_kernels(recorded):
    tr, steps, _ = recorded
    dev = next(iter(tr.ops))
    n_decode = len(devtrace.programs(tr, dev, "decode"))
    assert n_decode == len(steps) > 0
    # one decode-attention call per layer and step
    lo, hi = tr.window
    calls = [o for o in devtrace.overlapping(tr.ops[dev], lo, hi)
             if devtrace.kernel_kind(o[0]) == "decode"]
    assert len(calls) == 28 * n_decode
    assert 0 < devtrace.busy_ns(tr, dev) < hi - lo
    # and the prefills, by the flash kernel
    n_prefill = len(devtrace.programs(tr, dev, "prefill"))
    flash = [o for o in devtrace.overlapping(tr.ops[dev], lo, hi)
             if devtrace.kernel_kind(o[0]) == "prefill"]
    assert n_prefill > 0 and len(flash) == 28 * n_prefill


def test_recorded_trace_readers(recorded):
    tr, steps, cell = recorded
    ctx = SimpleNamespace(trace=tr, devices=list(tr.ops),
                          work={"steps": steps, "prefills": {}},
                          model=cell.config,
                          peaks=spec.peaks("TPU v5 lite"))
    readers = {m.name: m.read for m in cell.per_layer}
    for name in DEVICE_READERS:
        v = readers[name](ctx)
        assert v is not None and 0 < v <= 100, (name, v)
    # the decode step's bytes can not beat the bandwidth; its operations
    # are a sliver of the peak
    assert readers["decode.mfu"](ctx) < readers["decode.hbm_share"](ctx)


def test_readers_read_nothing_without_a_device():
    cell = spec.load_cell(BENCHMARK, "qwen3-0.6b.paper-mix")
    empty = devtrace.Trace(window=(0.0, 1e9))
    ctx = SimpleNamespace(trace=empty, devices=["TPU:0"],
                          work={"steps": {}, "prefills": {}},
                          model=cell.config, peaks=None)
    readers = {m.name: m.read for m in cell.per_layer}
    for name in DEVICE_READERS:
        assert readers[name](ctx) is None
