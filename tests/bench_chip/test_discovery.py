"""A configuration, a traffic mix and a per-layer metric are added as
files plus entries in BENCHMARK.json, and the harness finds them by
name, with no edit to any file the benchmark already has."""
import hashlib
import json
import os
import shutil
from types import SimpleNamespace

from bench_chip_paths import BENCHMARK, CHIP, load

spec = load("spec")
traffic = load("traffic")


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = _digests(chip)
    bench = json.load(open(BENCHMARK))
    # a throwaway configuration: qwen3-0.6b with 8 slots
    cfg = json.load(open(chip / "configs" / "qwen3-0.6b.json"))
    cfg.update(name="toy-8slot", n_slots=8)
    (chip / "configs" / "toy-8slot.json").write_text(json.dumps(cfg))
    # a throwaway mix: one task, bursty arrivals
    mix = {"name": "toy-mix", "tasks": [
        {"name": "chat", "weight": 1, "prompt": [4.0, 0.3],
         "decode": [4.0, 0.3]}],
        "prompt_clip": [4, 500], "decode_clip": [1, 500],
        "prompt_grid": [32, 64, 128, 256], "arrivals": "bursty",
        "rate_per_replica": 3.0, "warmup_s": 1.0, "horizon_s": 10.0,
        "sizes_seed": 1, "shuffle_block": 8}
    (chip / "traffic" / "toy-mix.json").write_text(json.dumps(mix))
    # a throwaway per-layer metric
    (chip / "metrics" / "toy_count.py").write_text(
        "def read(ctx):\n    return float(len(ctx.rec.adv_end))\n")
    bench["configs"].append({
        "name": "toy-8slot", "source": "https://huggingface.co/Qwen/"
        "Qwen3-0.6B", "file": "benchmarks/chip/configs/toy-8slot.json",
        "reduced": [], "why": "throwaway"})
    bench["workloads"].append({
        "name": "toy-8slot.toy-mix", "config": "toy-8slot",
        "traffic": "toy-mix", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({
        "name": "toy_count", "unit": "advances", "better": "higher",
        "source": "host_clock", "layer": "gateway: cluster adapter",
        "moves": "itl_p95_ms", "workloads": ["toy-8slot.toy-mix"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))

    cell = spec.load_cell(str(path), "toy-8slot.toy-mix", here=str(chip))
    assert cell.config["n_slots"] == 8 and cell.chips == 1
    assert cell.traffic["name"] == "toy-mix"
    names = [m.name for m in cell.per_layer]
    assert "toy_count" in names and "route_ms" not in names
    assert [m.name for m in cell.end_to_end] == [
        m["name"] for m in bench["end_to_end"] if "workloads" not in m]
    reader = next(m.read for m in cell.per_layer if m.name == "toy_count")
    assert reader(SimpleNamespace(rec=SimpleNamespace(
        adv_end=[1, 2, 3]))) == 3.0
    draws = traffic.stream(cell.traffic, seed=2**31 + 5, replicas=1,
                           cache_len=cell.config["cache_len"], vocab=100)
    assert {d.task for d in draws} == {"chat"}
    assert {d.prompt for d in draws} <= {32, 64, 128, 256}
    # the old cells still load, and no file that was there changed
    old = spec.load_cell(str(path), "qwen3-0.6b.paper-mix", here=str(chip))
    assert "toy_count" not in [m.name for m in old.per_layer]
    after = _digests(chip)
    assert {k: after[k] for k in before} == before


def test_missing_parts_are_errors(tmp_path):
    bench = json.load(open(BENCHMARK))
    bench["workloads"].append({"name": "x.nomix", "config": "qwen3-0.6b",
                               "traffic": "nomix", "chips": 1, "why": "-"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    os.symlink(os.path.dirname(os.path.dirname(CHIP)) + "/benchmarks",
               tmp_path / "benchmarks")
    assert spec.load_cell(str(path), "qwen3-0.6b.paper-mix", here=CHIP)
    for cell in ("x.nomix", "no-such-cell"):
        try:
            spec.load_cell(str(path), cell, here=CHIP)
        except spec.SpecError:
            continue
        raise AssertionError(f"{cell} loaded")
    try:
        spec.peaks("TPU v99")
    except spec.SpecError:
        pass
    else:
        raise AssertionError("an unknown device had peaks")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
