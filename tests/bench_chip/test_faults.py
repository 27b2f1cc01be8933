"""A run whose timed path is broken underneath has to come out not
correct.  Each fault is planted in the served path of a CPU rehearsal
(the program's small preset, past the look for a chip), and the rest of
the run, the reference check with it, goes on as usual:

  state_unchanged  the decode step hands back its KV cache unwritten
  half_batch       every other active row of each decode step is left
                   out and given the mean of the other active rows' logits
  altered_token    one token of every request is changed where the
                   engine produces it
  served_twice     the cluster reports one finished request twice

The cell's replicas share nothing, so there is no exchange between
chips to leave out.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from bench_chip_paths import CHIP, load

run = load("run")
CELL = "qwen3-0.6b.paper-mix"
WARMUP_S = load("traffic").load(
    f"{CHIP}/traffic/paper-mix.json")["warmup_s"]


def state_unchanged(engines, cluster):
    from repro.models import model as model_lib
    for e in engines:
        cfg = e.cfg
        e.decode_fn = jax.jit(
            lambda p, c, t, cfg=cfg: (model_lib.decode_step(
                p, cfg, c, tokens=t)[0], c))


def half_batch(engines, cluster):
    for e in engines:
        def step(p, c, t, orig=e.decode_fn, e=e):
            logits, c = orig(p, c, t)
            active = [i for i, r in enumerate(e.slots) if r is not None]
            out, kept = active[1::2], active[0::2]
            if not out:
                return logits, c
            return logits.at[jnp.array(out)].set(
                logits[jnp.array(kept)].mean(0)), c
        e.decode_fn = step


def altered_token(engines, cluster):
    for e in engines:
        def decode(orig=e._decode_iteration, e=e):
            active = [r for r in e.slots if r is not None]
            done = orig()
            for r in active:
                if len(r.output_tokens) == 3:
                    r.output_tokens[2] = (r.output_tokens[2] + 1) \
                        % e.cfg.vocab_size
            return done
        e._decode_iteration = decode


def served_twice(engines, cluster):
    """Once the window is open (past the warm-up), one finished request
    is reported done twice."""
    orig = cluster.advance
    dup = []

    @functools.wraps(orig)
    def advance():
        done = orig()
        if done and not dup and cluster.t > WARMUP_S + 0.1:
            dup.append(done[0])
            return done + [done[0]]
        return done
    cluster.advance = advance


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   altered_token, served_twice],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    args = run.parse(["--workload", CELL, "--seed", str(2**31 + 99),
                      "--seconds", "6", "--trace", "0"])
    result = run.run(args, rehearse=True, break_with=fault)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_sound_run_is_correct():
    args = run.parse(["--workload", CELL, "--seed", str(2**31 + 99),
                      "--seconds", "6", "--trace", "0"])
    result = run.run(args, rehearse=True)
    assert result["correct"] is True
    assert jnp.isfinite(result["checks"]["logit_gap"]["value"])
