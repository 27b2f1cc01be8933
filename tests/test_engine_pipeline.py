"""The engine's decode pipeline on CPU: the next step's input ids stay on
the device, each decode step is dispatched before the host waits for
its inputs (the ids the step before made), and a prefill fetches
nothing.

Against a synchronous greedy loop written here (prefill, argmax on the
host, upload, decode) the served ids, the virtual-clock stamps and the
steps at which requests complete are the same, through an idle spell,
preemption at the capacity limit, ``fail``/``recover`` and ``steal``
with a step in flight; an MoE engine's routing sums cover every
dispatched step whenever they are read.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import V100_LLAMA2_7B
from repro.models import params as params_lib
from repro.serving.engine import LLMInstance
from repro.serving.request import Phase, Request
from repro.serving.scheduler import FCFS

PROF = V100_LLAMA2_7B
DENSE = get_config("qwen3-0.6b").reduced()
MOE = get_config("deepseek-v2-lite").reduced()


class SyncInstance(LLMInstance):
    """The synchronous greedy loop: prefill, argmax on the host, upload,
    decode.  Every row is fed the argmax of its last logits, free rows
    too, so that an MoE step routes the same rows as the engine's."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.host_ids = np.zeros(self.n_slots, np.int32)
        self.step_counts = []

    def _admit(self, req, slot):
        toks = jax.device_put(np.asarray(req.tokens, np.int32)[None],
                              self.device)
        logits, small = self.prefill_fn(self.params, toks)
        self.cache = self.insert_fn(self.cache, small, slot)
        self.host_ids[slot] = np.argmax(np.asarray(logits[0]))
        self.slots[slot] = req
        req.phase = Phase.DECODE
        req.prefilled = req.prompt_tokens
        req.prefill_done = self.clock

    def _decode_iteration(self):
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return []
        out = self.decode_fn(self.params, self.cache,
                             jax.device_put(self.host_ids, self.device))
        logits, self.cache = out[:2]
        self.step_counts += [np.asarray(c) for c in out[2:]]
        ids = self.host_ids
        self.host_ids = np.argmax(np.asarray(logits), -1).astype(np.int32)
        done = []
        for i in active:
            r = self.slots[i]
            r.decoded += 1
            if r.first_token is None:
                r.first_token = self.clock
            r.token_times.append(self.clock)
            r.output_tokens.append(int(ids[i]))
            if r.decoded >= r.decode_tokens:
                r.phase = Phase.DONE
                r.finished = self.clock
                self.completed.append(r)
                self.slots[i] = None
                done.append(r)
        return done


@pytest.fixture(scope="module")
def dense_params():
    return params_lib.init_params(jax.random.PRNGKey(0), DENSE)


@pytest.fixture(scope="module")
def moe_params():
    return params_lib.init_params(jax.random.PRNGKey(3), MOE)


def _requests(cfg, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt_tokens=p, decode_tokens=d,
                    tokens=rng.integers(0, cfg.vocab_size, p).tolist())
            for p, d in shapes]


def _run_until_done(eng, reqs, log, max_steps=500):
    for _ in range(max_steps):
        if all(r.finished is not None for r in reqs):
            return
        log.append([reqs.index(r) for r in eng.step()])
    raise AssertionError("requests did not finish")


# scenarios: each serves its requests on one engine and returns, per
# engine iteration, the indices of the requests completed in it

def idle_spell(eng, reqs):
    log = []
    for r in reqs[:3]:
        eng.submit(r)
    _run_until_done(eng, reqs[:3], log)
    for _ in range(3):                       # nothing resident or queued
        log.append([reqs.index(r) for r in eng.step()])
    for r in reqs[3:]:
        eng.submit(r)
    _run_until_done(eng, reqs, log)
    return log


def preempt(eng, reqs):
    log = []
    for r in reqs:
        eng.submit(r)
    _run_until_done(eng, reqs, log)
    assert any(r.preemptions for r in reqs), "the scenario must preempt"
    return log


def fail_recover(eng, reqs):
    log = []
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        log.append([reqs.index(r) for r in eng.step()])
    orphans = eng.fail()
    assert orphans
    log.append([reqs.index(r) for r in eng.step()])
    eng.recover()
    for r in orphans:
        eng.submit(r)
    _run_until_done(eng, reqs, log)
    return log


def steal(eng, reqs):
    log = []
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        log.append([reqs.index(r) for r in eng.step()])
    taken = next(r for r in eng.slots if r is not None)
    assert eng.steal(taken)                  # its last step is in flight
    log.append([reqs.index(r) for r in eng.step()])
    eng.submit(taken)
    _run_until_done(eng, reqs, log)
    return log


SHAPES = [(12, 9), (20, 5), (7, 14), (16, 3), (9, 8), (30, 6)]
SCENARIOS = [
    (idle_spell, {}, SHAPES),
    (preempt, {"capacity_tokens": 100}, [(40, 20)] * 3),
    (fail_recover, {}, SHAPES[:4]),
    (steal, {}, SHAPES[:4]),
]


def _serve(cls, cfg, params, scenario, prof_kw, shapes):
    prof = dataclasses.replace(PROF, **prof_kw)
    eng = cls(cfg, params, prof, FCFS(), n_slots=3, cache_len=64)
    reqs = _requests(cfg, shapes)
    log = scenario(eng, reqs)
    return eng, reqs, log


def _same_service(a, b):
    (_, reqs_a, log_a), (_, reqs_b, log_b) = a, b
    assert log_a == log_b
    for ra, rb in zip(reqs_a, reqs_b):
        assert ra.output_tokens == rb.output_tokens
        assert len(ra.output_tokens) == ra.decode_tokens
        assert ra.token_times == rb.token_times
        assert (ra.first_token, ra.prefill_done, ra.finished,
                ra.preemptions) == (rb.first_token, rb.prefill_done,
                                    rb.finished, rb.preemptions)


@pytest.mark.parametrize("scenario,prof_kw,shapes", SCENARIOS,
                         ids=lambda s: getattr(s, "__name__", ""))
def test_pipeline_serves_what_the_sync_loop_serves(dense_params, scenario,
                                                   prof_kw, shapes):
    _same_service(
        _serve(LLMInstance, DENSE, dense_params, scenario, prof_kw, shapes),
        _serve(SyncInstance, DENSE, dense_params, scenario, prof_kw,
               shapes))


@pytest.mark.parametrize("scenario", [idle_spell, steal],
                         ids=lambda s: s.__name__)
def test_moe_pipeline_serves_and_counts_as_the_sync_loop(moe_params,
                                                         scenario):
    shapes = SHAPES[:4] if scenario is steal else SHAPES
    pipe = _serve(LLMInstance, MOE, moe_params, scenario, {}, shapes)
    sync = _serve(SyncInstance, MOE, moe_params, scenario, {}, shapes)
    _same_service(pipe, sync)
    eng, counts = pipe[0], sync[0].step_counts
    assert eng.moe_steps == len(counts) == eng.decode_steps
    assert eng.moe_held_pairs == sum(int(c.sum()) for c in counts)
    assert eng.moe_experts_hit == sum(np.count_nonzero(c) for c in counts)


def test_moe_sums_cover_every_dispatched_step_when_read(moe_params):
    """Read after 1, 2, 3 ... decode iterations, the running sums equal
    the counts of every step dispatched so far, the last one's too."""
    eng = LLMInstance(MOE, moe_params, PROF, FCFS(), n_slots=3,
                      cache_len=64)
    seen = []
    orig = eng.decode_fn

    def decode(p, c, t):
        out = orig(p, c, t)
        seen.append(out[2])
        return out
    eng.decode_fn = decode
    for slot, r in enumerate(_requests(MOE, [(9, 40), (17, 40),
                                             (30, 40)])):
        eng._admit(r, slot)
    for gap in (1, 2, 3, 1, 4):
        for _ in range(gap):
            eng._decode_iteration()
        got = np.stack([np.asarray(c) for c in seen])
        assert eng.moe_steps == len(seen)
        assert eng.moe_held_pairs == got.sum()
        assert eng.moe_experts_hit == sum(np.count_nonzero(c) for c in got)


def test_step_is_dispatched_before_the_ids_before_it_are_read(
        dense_params, monkeypatch):
    """The host reads only the inputs of decode steps it has dispatched,
    once each, after their dispatch and before the next; a prefill reads
    nothing; every step but the first after an empty spell is pipelined."""
    eng = LLMInstance(DENSE, dense_params, PROF, FCFS(), n_slots=3,
                      cache_len=64)
    log, inputs = [], []
    orig_decode, orig_admit = eng.decode_fn, eng._admit

    def decode(p, c, t):
        inputs.append(t)                     # held, so ids stay unique
        log.append(("dispatch", id(t)))
        return orig_decode(p, c, t)

    def admit(req, slot):
        log.append(("admit",))
        orig_admit(req, slot)
        log.append(("admitted",))
    eng.decode_fn, eng._admit = decode, admit
    array_type = type(eng.tokens)
    value, buffer = array_type._value, array_type.__buffer__

    def read_value(self):
        log.append(("read", id(self)))
        return value.fget(self)

    def read_buffer(self, flags):
        log.append(("read", id(self)))
        return buffer(self, flags)
    monkeypatch.setattr(array_type, "_value", property(read_value))
    monkeypatch.setattr(array_type, "__buffer__", read_buffer)

    reqs = _requests(DENSE, SHAPES)
    starts = 0
    waves = (reqs[:3], reqs[3:])
    for wave in waves:
        for r in wave:
            eng.submit(r)
        for _ in range(200):
            if all(r.finished is not None for r in wave):
                break
            empty, before = not eng.resident, eng.decode_steps
            eng.step()
            starts += empty and eng.decode_steps > before
    monkeypatch.undo()
    assert all(r.finished is not None for r in reqs)

    # no read between the start and the end of an admission
    inside = False
    for entry in log:
        if entry[0] in ("admit", "admitted"):
            inside = entry[0] == "admit"
        assert not (inside and entry[0] == "read"), "a prefill read ids"
    # dispatch k, read of step k's input, dispatch k+1, ...
    seq = [e for e in log if e[0] in ("dispatch", "read")]
    assert len(seq) == 2 * eng.decode_steps
    for k in range(eng.decode_steps):
        (d, x), (rd, y) = seq[2 * k], seq[2 * k + 1]
        assert (d, rd) == ("dispatch", "read") and x == y
    assert starts == len(waves)
    assert eng.pipelined_steps == eng.decode_steps - starts
    assert eng.pipelined_steps > 0.8 * eng.decode_steps
