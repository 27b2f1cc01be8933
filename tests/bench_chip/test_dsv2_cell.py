"""The ``deepseek-v2-lite-ep4.paper-mix-full`` cell: its CPU rehearsal
through ``run.py``, its reference's weights and sizes, its operation and
byte counts at the published config against arithmetic done by hand,
and its two per-layer readers and the module-name finder on a small
synthetic trace."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_paths import BENCHMARK, CHIP, load

run = load("run")
spec = load("spec")
devtrace = load("devtrace")
modules = load("modules")
flops = load("flops_mla_moe")
ref = spec.reference("deepseek-v2-lite")

CELL = "deepseek-v2-lite-ep4.paper-mix-full"
FILE = json.load(open(os.path.join(CHIP, "configs",
                                   "deepseek-v2-lite-ep4.json")))
V5E = spec.peaks("TPU v5 lite")


def test_rehearsal_is_correct_and_reports_its_metrics():
    """The cell's traffic through the gateway and an engine of the
    program's small preset, with the fp8 control read on the same
    sample: correct, nothing compiled in the window, the end-to-end
    metrics exactly itl_p95_ms and setup_s, and the control past the
    limit that the program stays under."""
    args = run.parse(["--workload", CELL, "--seed", str(2**31 + 8765),
                      "--seconds", "5"])
    res = run.run(args, rehearse=True, control=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["info"]["window_compiles"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    limit = res["checks"]["logit_gap"]["limit"]
    assert res["checks"]["logit_gap"]["value"] <= limit
    assert res["control"]["logit_gap"] > limit


def test_the_cell_reads_only_its_metrics():
    cell = spec.load_cell(BENCHMARK, CELL)
    assert cell.chips == 1 and cell.config["n_slots"] == 32
    assert [m.name for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["dsv2_decode.hbm_share",
                                                "dsv2_decode.mfu"]


def test_weights_are_the_served_ones():
    from repro.configs import get_config
    from repro.models import params as params_lib
    cfg = get_config("deepseek-v2-lite").reduced(dtype="bfloat16")
    seed = 2**31 + 99
    served = params_lib.init_params(jax.random.PRNGKey(seed), cfg)
    mine = ref.weights(seed, ref.config_of(cfg))
    first, layers = served["first_layer"], served["layers"][0]
    pairs = [(served["embed"], mine["embed"]),
             (served["lm_head"], mine["head"]),
             (first["ffn"]["w_down"], mine["first"]["ffn"]["w_down"])]
    pairs += [(first["attn"][k], mine["first"]["attn"][k])
              for k in ("wq", "wkv_a", "wkv_b", "wo")]
    pairs += [(layers["attn"][k], mine["layers"]["attn"][k])
              for k in ("wq", "wkv_a", "wkv_b", "wo")]
    moe = layers["moe"]
    pairs += [(moe["routed"][k], mine["layers"]["experts"][k])
              for k in ("w_up", "w_down", "w_gate")]
    pairs += [(moe["router"], mine["layers"]["router"]),
              (moe["shared"]["w_up"], mine["layers"]["shared"]["w_up"])]
    for a, b in pairs:
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))


def test_the_program_runs_the_file():
    import dataclasses
    from repro.configs import get_config
    served = dataclasses.replace(get_config("deepseek-v2-lite"),
                                 use_pallas=True)
    assert ref.departures(FILE, served) == []
    assert FILE["n_routed_experts"] == 16
    assert FILE["routed_experts_published"] == 64
    off = ref.departures(FILE, dataclasses.replace(
        served, moe=dataclasses.replace(served.moe, norm_topk=True)))
    assert off == ["norm_topk_prob: file False, program True"]
    assert ref.departures(FILE, dataclasses.replace(
        served, mla=dataclasses.replace(served.mla, rope_interleaved=False)))


# published config: per decoded token and layer, the attention's
# q 2048*16*192 + latent and rotary key 2048*576 + absorbed key and value
# parts 16*128*512 twice + o 16*128*2048
ATTN = 6_291_456 + 1_179_648 + 1_048_576 + 1_048_576 + 4_194_304
EXPERT = 3 * 2048 * 1408                  # one routed expert's SwiGLU
DENSE = 3 * 2048 * 10944                  # layer 0
SHARED = 3 * 2048 * 2 * 1408              # two shared experts
ROUTER = 2048 * 64


def test_layer_counts():
    assert flops.attn_matmul_params(FILE) == ATTN == 13_762_560
    assert flops.expert_params(FILE) == EXPERT == 8_650_752
    assert flops.moe_layers(FILE) == 26
    # 27 layers x (512 latent + 64 rotary) x 2 bytes
    assert flops.cache_bytes_per_token(FILE) == 31_104


def test_non_expert_bytes_and_the_program_count():
    # per layer: wq, wkv_a, wkv_b (512*16*256), wo and three norms
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 + 2 * 2048 + 512
    bf16 = 27 * attn + DENSE + 26 * SHARED + 2048 + 2048 * 102_400
    assert flops.non_expert_bytes(FILE) == 2 * bf16 + 4 * 26 * ROUTER \
        == 2_210_651_136
    # with the held experts, the routers and the embedding: the
    # program's count of what the chip holds
    from repro.configs import get_config
    held = 26 * 16 * EXPERT
    assert bf16 + 26 * ROUTER + held + 2048 * 102_400 == get_config(
        "deepseek-v2-lite").count_params() == 4_910_345_728


def test_decode_step_two_rows():
    ops, nbytes = flops.decode_step(FILE, [100, 200], held_pairs=10,
                                    experts_hit=7)
    per_row = 2 * (27 * ATTN + DENSE + 26 * (SHARED + ROUTER)
                   + 2048 * 102_400)
    # absorbed scores over 512 latent + 64 rotary, values over 512, per
    # head, layer and position attended
    attn = 2 * 27 * 16 * (512 + 64 + 512) * 300
    assert ops == 2 * per_row + attn + 2 * EXPERT * 10 == 4_862_189_568
    # weights outside the experts, 7 held experts, 300 positions read and
    # 2 written, 2 embedding rows read and 2 logit rows written
    assert nbytes == (2_210_651_136 + 2 * EXPERT * 7 + 31_104 * 302
                      + 2 * 2 * (2048 + 102_400)) == 2_341_572_864


def _toy_trace():
    mods = {"TPU:0": [("jit_prefill(11)", 0, 40),
                      ("jit_decode_step(7)", 50, 90),
                      ("jit__argmax(3)", 90, 92),
                      ("jit_decode_step(7)", 100, 160),
                      ("jit_decode_step(7)", 300, 330)]}
    ops = {"TPU:0": [("%fusion.1 = bf16[32] fusion(%p.1)", 50, 90)]}
    return devtrace.Trace(ops=ops, modules=mods, host=[], window=(0, 200))


def test_decode_programs_are_found_by_module_name():
    tr = _toy_trace()
    assert modules.module_name("jit_decode_step(7)") == "jit_decode_step"
    assert modules.programs(tr, "TPU:0", "decode_step") == [
        ("jit_decode_step(7)", 50, 90), ("jit_decode_step(7)", 100, 160)]
    assert [m[0] for m in modules.programs(tr, "TPU:0", "prefill")] == [
        "jit_prefill(11)"]
    assert modules.seconds(tr, ["TPU:0"], "decode_step") == \
        pytest.approx(100e-9)
    # no decode-attention kernel in it: the kernel finder sees nothing
    assert devtrace.programs(tr, "TPU:0", "decode") == []


def _ctx(engines):
    steps = {(0, 0.1): [100, 200], (0, 0.3): [101, 201]}
    return SimpleNamespace(
        trace=_toy_trace(), devices=["TPU:0"],
        work={"steps": steps, "prefills": {}}, model=FILE, peaks=V5E,
        rec=SimpleNamespace(engines=engines))


def test_readers_on_a_synthetic_trace():
    cell = spec.load_cell(BENCHMARK, CELL)
    readers = {m.name: m.read for m in cell.per_layer}
    # 4 steps of 2 rows: 80 pairs reached held experts, 28 held experts
    # reached: 10 pairs a row and 7 experts a step
    eng = SimpleNamespace(moe_steps=4, n_slots=2, moe_held_pairs=80,
                          moe_experts_hit=28)
    ctx = _ctx([eng])
    t = 100e-9
    want = [flops.decode_step(FILE, c, 20, 7)
            for c in ctx.work["steps"].values()]
    assert readers["dsv2_decode.hbm_share"](ctx) == pytest.approx(
        100 * sum(b for _, b in want) / (t * 819e9))
    assert readers["dsv2_decode.mfu"](ctx) == pytest.approx(
        100 * sum(o for o, _ in want) / (t * 197e12))
    # a program whose engines keep no routing sums, or no device, reads
    # nothing
    for ctx in (_ctx([SimpleNamespace(n_slots=2)]), _ctx([])):
        assert readers["dsv2_decode.hbm_share"](ctx) is None
        assert readers["dsv2_decode.mfu"](ctx) is None
    ctx = _ctx([eng])
    ctx.peaks = None
    assert readers["dsv2_decode.hbm_share"](ctx) is None
