"""The plain reference of the qwen3-0.6b configurations: it draws the
served weights itself, bit for bit, and its forward agrees with the
program's float32 forward; the program is held to the file's sizes."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_paths import CHIP, load

spec = load("spec")
ref = spec.reference("qwen3-0.6b")
FILE = json.load(open(os.path.join(CHIP, "configs", "qwen3-0.6b.json")))


def _small():
    from repro.configs import get_config
    pub = get_config("qwen3-0.6b")
    return [pub.reduced(), dataclasses.replace(pub.reduced(),
                                               dtype="bfloat16")]


@pytest.mark.parametrize("cfg", _small(), ids=lambda c: c.dtype)
def test_weights_are_the_served_ones(cfg):
    from repro.models import params as params_lib
    seed = 2**31 + 99
    served = params_lib.init_params(jax.random.PRNGKey(seed), cfg)
    mine = ref.weights(seed, ref.config_of(cfg))
    layer = served["layers"][0]
    pairs = [(served["embed"], mine["embed"])]
    pairs += [(layer["attn"][k], mine["layers"][k])
              for k in ("wq", "wk", "wv", "wo")]
    pairs += [(layer["ffn"][k], mine["layers"][k])
              for k in ("w_up", "w_down", "w_gate")]
    for a, b in pairs:
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b))


def test_forward_agrees_with_the_program():
    from repro.models import model as model_lib
    from repro.models import params as params_lib
    cfg = _small()[0]
    params = params_lib.init_params(jax.random.PRNGKey(3), cfg)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        want, _ = model_lib.forward_train(params, cfg,
                                          tokens=jnp.asarray(toks)[None])
    got = ref.logits(ref.weights(3, ref.config_of(cfg)),
                     ref.config_of(cfg), toks, 64)
    want = np.asarray(want[0])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_control_weights_are_fp8():
    cfg = ref.config_of(_small()[0])
    w = ref.weights(5, cfg)
    q = ref.fp8_weights(w)
    for a, b in [(w["embed"], q["embed"])] + [
            (w["layers"][k], q["layers"][k]) for k in w["layers"]]:
        a, b = np.asarray(a), np.asarray(b)
        rel = np.abs(a - b).max() / np.abs(a).max()
        assert 1e-4 < rel < 2 ** -3      # e4m3: 3 mantissa bits


def test_the_program_runs_the_file():
    from repro.configs import get_config
    served = dataclasses.replace(get_config("qwen3-0.6b"), use_pallas=True)
    assert ref.departures(FILE, served) == []
    off = ref.departures(FILE, dataclasses.replace(served, n_layers=27))
    assert off == ["num_hidden_layers: file 28, program 27"]
    assert ref.departures(FILE, dataclasses.replace(
        served, kv_cache_dtype="int8"))
