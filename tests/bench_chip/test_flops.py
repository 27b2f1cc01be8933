"""The benchmark's operation and byte counts at qwen3-0.6b's shapes,
against arithmetic done by hand from the published config."""
import json
import os

import pytest

from bench_chip_paths import CHIP, load

flops = load("flops")

QWEN3 = json.load(open(os.path.join(CHIP, "configs", "qwen3-0.6b.json")))
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

# per layer, per token: q 1024*16*128 + k, v 2*1024*8*128 + o 16*128*1024
# + gate, up, down 3*1024*3072
LAYER = 2_097_152 + 2_097_152 + 2_097_152 + 9_437_184


def test_layer_weights():
    assert flops.layer_matmul_params(QWEN3) == LAYER == 15_728_640


def test_param_bytes_match_the_published_count():
    # embedding (tied head) + 28 layers with two d-norms and two
    # head_dim-norms + final norm: 596,049,920 parameters, bf16
    n = 151_936 * 1024 + 28 * (LAYER + 2 * 1024 + 2 * 128) + 1024
    assert n == 596_049_920
    assert flops.param_bytes(QWEN3) == 2 * n


def test_param_bytes_match_the_program():
    from repro.configs import get_config
    assert flops.param_bytes(QWEN3) == 2 * get_config(
        "qwen3-0.6b").count_params()


def test_kv_bytes_per_token():
    # 28 layers x (k, v) x 8 heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(QWEN3) == 114_688


def test_decode_step_two_rows():
    ops, nbytes = flops.decode_step(QWEN3, [100, 200])
    # 2 rows x 2 x (28 layers' weights + the 1024 x 151936 head)
    matmul = 2 * 2 * (28 * LAYER + 1024 * 151_936)
    # q.k and p.v: 4 x 28 layers x 16 heads x 128 x (100 + 200) keys
    attn = 4 * 28 * 16 * 128 * 300
    assert ops == matmul + attn == 2_452_750_336
    # weights once, 300 keys read + 2 written, 2 embedding rows read and
    # 2 logit rows written
    assert nbytes == (1_192_099_840 + 114_688 * 302
                      + 2 * 2 * (1024 + 151_936)) == 1_227_347_456


def test_decode_attention_one_layer():
    ops, nbytes = flops.decode_attention(QWEN3, [100, 200])
    assert ops == 4 * 16 * 128 * 300 == 2_457_600
    # k and v of 300 positions x 8 heads x 128, q and out of 2 rows x 16
    # heads x 128, bf16
    assert nbytes == 2 * (2 * 8 * 128 * 300 + 2 * 16 * 128 * 2) == 1_245_184


def test_prefill_counts_causal_pairs():
    ops, nbytes = flops.prefill(QWEN3, 4)
    assert ops == (2 * 4 * 28 * LAYER + 4 * 28 * 16 * 128 * 10
                   + 2 * 1024 * 151_936)
    assert nbytes == 1_192_099_840 + 114_688 * 4 + 2 * (4 * 1024 + 151_936)


def test_least_time_is_the_larger_bound():
    ops, nbytes = flops.decode_attention(QWEN3, [2048] * 16)
    t = flops.least_time(ops, nbytes, V5E)
    assert t == pytest.approx(nbytes / 819e9)      # bandwidth bound
    assert t > ops / 197e12
    assert flops.least_time(197e12, 1.0, V5E) == pytest.approx(1.0)
