"""A real JAX LLM-instance engine: slot-based continuous batching over the
model zoo, with a virtual clock driven by the calibrated hardware profile.

One ``LLMInstance`` = one model replica (on the production mesh: one
"model"-axis slice).  It owns
  * a jitted prefill (batch-1) + slot-insert + gang decode step,
  * a local admission queue ordered by an instance-level scheduler
    (FCFS / bin-packing / least-work-left),
  * a paged-token capacity budget with preemption (newest-first eviction,
    as in vLLM) when decode growth overflows the budget,
  * per-request lifecycle metrics (TTFT / TBT / E2E) on the virtual clock.

On a TPU the replicas run at published widths, one per chip
(``device=``), with the Pallas kernels; on CPU (examples, tests) they run
reduced configs.  The discrete-event simulator in ``repro.core.simulator``
reproduces the paper's timing experiments at scale using the same
Request/scheduler abstractions.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.core.prefix_cache import PrefixCache
from repro.core.profiles import HardwareProfile
from repro.models import model as model_lib
from repro.serving import trace as _trace
from repro.serving.request import Phase, Request
from repro.serving.scheduler import InstanceScheduler


def _greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _place(ids, logits, slot):
    return ids.at[slot].set(_greedy(logits[0]))


# the next decode step's input ids stay on the device: a decode step's
# are the argmax of its logits, and a prefill's first token is put at
# its slot; neither is read by the host before the next step is queued
next_ids = jax.jit(_greedy)
place_first = jax.jit(_place)


@functools.lru_cache(maxsize=32)
def _build_fns(cfg: ModelConfig, cache_len: int):
    """The jitted prefill, slot insert and decode step, named so that
    XLA's modules and compile logs say which is which."""
    def prefill(params, tokens):
        return model_lib.prefill(params, cfg, tokens=tokens,
                                 cache_len=cache_len)

    def insert(cache, new, slot):
        def one(path, full, small):
            names = [str(getattr(k, "key", "")) for k in path]
            axis = 1 if "layers" in names else 0
            start = [0] * full.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(full, small.astype(
                full.dtype), tuple(start))
        out = jax.tree_util.tree_map_with_path(one, cache, new)
        return out

    def decode_step(params, cache, toks):
        # MoE configs also return the tokens routed to each held expert
        return model_lib.decode_step(params, cfg, cache, tokens=toks,
                                     routed=cfg.moe is not None)

    return (jax.jit(prefill), jax.jit(insert, donate_argnums=(0,)),
            jax.jit(decode_step, donate_argnums=(1,)))


class LLMInstance:
    def __init__(self, cfg: ModelConfig, params, profile: HardwareProfile,
                 scheduler: InstanceScheduler, n_slots: int = 8,
                 cache_len: int = 256, instance_id: int = 0,
                 prefix_cache_tokens: int = 0, prefix_block: int = 32,
                 device: Optional[jax.Device] = None):
        assert cfg.input_mode == "tokens", "engine path uses token inputs"
        # params and KV cache live on ``device`` (the default device when
        # None); the jitted steps follow their committed inputs there
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.cfg, self.params, self.profile = cfg, params, profile
        self.scheduler = scheduler
        self.n_slots, self.cache_len = n_slots, cache_len
        self.instance_id = instance_id
        self.prefill_fn, self.insert_fn, self.decode_fn = _build_fns(
            cfg, cache_len)
        with jax.default_device(device):
            self.cache = jax.device_put(
                model_lib.init_cache(cfg, n_slots, cache_len), device)
        self.slots: List[Optional[Request]] = [None] * n_slots
        # int32 [n_slots] on the device: the input ids of the next decode
        # step (free rows hold whatever the last step made of them)
        self.tokens = jax.device_put(np.zeros((n_slots,), np.int32),
                                     device)
        self.queue: deque = deque()
        self.clock = 0.0
        self.completed: List[Request] = []
        self.failed = False
        # straggler model: scales every virtual-clock iteration time
        # (1.0 = nominal), same semantics as SimInstance.speed_factor
        self.speed_factor = 1.0
        # prefix/KV cache model (core.prefix_cache): the real prefill
        # still runs in full (model correctness -- the reduced configs
        # here don't share KV across slots), but the VIRTUAL clock
        # charges only the uncached suffix, which is the quantity the
        # simulator's fidelity harness validates.
        self.prefix_cache = (PrefixCache(prefix_cache_tokens,
                                         prefix_block)
                             if prefix_cache_tokens > 0 else None)
        # lifecycle tracing (serving.trace).  The virtual clock is
        # advanced BEFORE the decode pass (see step()), so first-token
        # and completion stamps land at the iteration's END -- the same
        # anchor as the simulator, letting fidelity deltas compare
        # like-for-like.  ``trace_instance`` is the id used in events --
        # EngineClusterAdapter.set_trace rewrites it to the adapter
        # index so lanes line up with the gateway's routing ids.
        self.trace = _trace.NULL
        self.trace_instance = instance_id
        # decode steps dispatched, and those of them dispatched while the
        # previous step's ids were not yet on the host
        self.decode_steps = self.pipelined_steps = 0
        # running sums of what the decode steps routed (MoE configs):
        # steps, (token, expert) pairs that reached a held expert, and
        # held experts that at least one pair reached, summed over MoE
        # layers; every row of a step counts, free slots too.  A step's
        # counts reach the host with the next step's fetch; reading the
        # sums drains those still on the device
        self.moe_steps = self._moe_pairs = self._moe_hit = 0
        self._moe_pending: list = []

    def _add_moe(self, counts):
        for c in counts:
            self._moe_pairs += int(c.sum())
            self._moe_hit += int(np.count_nonzero(c))

    def _drain_moe(self):
        self._add_moe(jax.device_get(self._moe_pending))
        self._moe_pending = []

    @property
    def moe_held_pairs(self) -> int:
        self._drain_moe()
        return self._moe_pairs

    @property
    def moe_experts_hit(self) -> int:
        self._drain_moe()
        return self._moe_hit

    # -- router-visible state ----------------------------------------------
    @property
    def resident(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def resident_tokens(self) -> int:
        return sum(r.total_context for r in self.resident)

    def free_tokens(self) -> int:
        return self.profile.capacity_tokens - self.resident_tokens() \
            - sum(r.prompt_tokens + r.decoded for r in self.queue)

    def load_summary(self) -> Dict:
        res = self.resident
        return {
            "n_resident": len(res),
            "n_queued": len(self.queue),
            "p_tokens": [r.prompt_tokens for r in res],
            "d_tokens": [r.decoded for r in res],
            "resident_tokens": self.resident_tokens(),
            "free_tokens": self.free_tokens(),
            "clock": self.clock,
        }

    # -- request intake ------------------------------------------------------
    def submit(self, req: Request):
        # the KV cache holds the prompt plus every decoded token but the
        # last; past cache_len the decode step would write nothing
        if req.prompt_tokens + req.decode_tokens > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_tokens} + decode "
                f"{req.decode_tokens} tokens exceed cache_len "
                f"{self.cache_len}")
        req.phase = Phase.INSTANCE_QUEUE
        req.instance = self.instance_id
        req.routed_at = self.clock
        self.queue.append(req)

    # -- one engine iteration -------------------------------------------------
    def step(self) -> List[Request]:
        """Admit + prefill (at most one request/iteration, vLLM-style) then
        gang-decode every active slot.  Returns requests completed at this
        iteration; advances the virtual clock."""
        if self.failed:
            return []
        with _trace.span("engine.step", replica=self.trace_instance,
                         rows=len(self.slots) - self.slots.count(None)):
            prefill_tokens = 0
            # admission (scheduler's choice among queued)
            free_slot = next(
                (i for i, s in enumerate(self.slots) if s is None), None)
            if free_slot is not None and self.queue:
                budget = self.profile.capacity_tokens - self.resident_tokens()
                pick = self.scheduler.pick(list(self.queue), budget,
                                           self.profile)
                if pick is not None:
                    req = self.queue[pick]
                    del self.queue[pick]
                    self._admit(req, free_slot)
                    cached = 0
                    if self.prefix_cache is not None and req.prefix_hashes:
                        cached = self.prefix_cache.admit(req.prompt_tokens,
                                                         req.prefix_hashes)
                        req.cached_prefix = cached
                    # cached prefix costs no prefill compute on the virtual
                    # clock; it re-enters iteration_time as resident
                    # context below (same split as SimInstance)
                    prefill_tokens += req.prompt_tokens - cached
                    if self.trace.enabled:
                        self.trace.emit(self.clock, _trace.EV_INST_ADMIT,
                                        req.rid, self.trace_instance,
                                        req.tenant, {"cached": int(cached)})
                        self.trace.emit(self.clock, _trace.EV_PREFILL_DONE,
                                        req.rid, self.trace_instance,
                                        req.tenant)
            # charge the iteration BEFORE running the decode pass: the
            # resident-context term is the pre-decode sum and the tokens
            # produced this iteration are stamped at its END, exactly like
            # SimInstance._iteration (TTFT/E2E anchors compare
            # like-for-like in the fidelity harness)
            resident_other = max(self.resident_tokens() - prefill_tokens, 0)
            self.clock += self.profile.iteration_time(
                prefill_tokens, resident_other) * self.speed_factor
            completions = self._decode_iteration()
            # capacity enforcement: evict newest-admitted if over budget
            while (self.resident_tokens() > self.profile.capacity_tokens
                   and len(self.resident) > 1):
                self._preempt_newest()
            return completions

    def _admit(self, req: Request, slot: int):
        req.wall_started = time.perf_counter()
        toks = req.tokens
        if toks is None:
            rng = np.random.default_rng(req.rid)
            toks = rng.integers(0, self.cfg.vocab_size,
                                size=(req.prompt_tokens,))
        with _trace.span("engine.prefill", rid=req.rid,
                         prompt=req.prompt_tokens):
            toks = jax.device_put(np.asarray(toks, np.int32)[None, :],
                                  self.device)
            logits, small = self.prefill_fn(self.params, toks)
            self.cache = self.insert_fn(self.cache, small, slot)
            self.tokens = place_first(self.tokens, logits, slot)
            self.slots[slot] = req
        req.phase = Phase.DECODE
        req.prefilled = req.prompt_tokens
        req.prefill_done = self.clock

    def _decode_iteration(self) -> List[Request]:
        active = [i for i, r in enumerate(self.slots) if r is not None]
        done: List[Request] = []
        if not active:
            return done
        with _trace.span("engine.decode"):
            # dispatch step n, then wait for its inputs (step n-1's
            # outputs, and a first token placed this iteration) while it
            # runs: the chip always has a step queued while the host waits
            toks = self.tokens
            out = self.decode_fn(self.params, self.cache, toks)
            logits, self.cache = out[:2]
            self.tokens = next_ids(logits)
            self.decode_steps += 1
            # a row that has decoded takes the previous step's output,
            # which is still on the device
            self.pipelined_steps += any(self.slots[i].decoded
                                        for i in active)
            counts = self._moe_pending        # earlier steps' routing counts
            self._moe_pending = list(out[2:])  # this step's (MoE configs)
            self.moe_steps += len(out) - 2
            with _trace.span("engine.fetch"):    # one sync for both
                ids, counts = jax.device_get((toks, counts))
            self._add_moe(counts)
            tr = self.trace
            for i in active:
                r = self.slots[i]
                r.decoded += 1
                if r.first_token is None:
                    r.first_token = self.clock
                    if tr.enabled:
                        tr.emit(self.clock, _trace.EV_FIRST_TOKEN, r.rid,
                                self.trace_instance, r.tenant)
                r.token_times.append(self.clock)
                r.output_tokens.append(int(ids[i]))
                if r.decoded >= r.decode_tokens:
                    r.phase = Phase.DONE
                    r.finished = self.clock
                    if tr.enabled:
                        tr.emit(self.clock, _trace.EV_COMPLETE, r.rid,
                                self.trace_instance, r.tenant)
                    if self.prefix_cache is not None and r.full_hashes:
                        self.prefix_cache.insert(r.full_hashes)
                    self.completed.append(r)
                    self.slots[i] = None
                    done.append(r)
        return done

    def _preempt_newest(self):
        cands = [(r.prefill_done or 0.0, i) for i, r in
                 enumerate(self.slots) if r is not None]
        if len(cands) <= 1:     # never evict the last resident (liveness)
            return
        _, i = max(cands)
        req = self.slots[i]
        self.slots[i] = None
        if self.trace.enabled:
            self.trace.emit(self.clock, _trace.EV_PREEMPT, req.rid,
                            self.trace_instance, req.tenant,
                            {"lost": int(req.prefilled + req.decoded)})
        req.reset_progress()
        self.queue.appendleft(req)

    # -- fault injection (cluster manager) -----------------------------------
    def fail(self) -> List[Request]:
        """Kill the instance; return in-flight + queued requests for
        re-routing (idempotent: their progress is reset)."""
        self.failed = True
        if self.trace.enabled:
            self.trace.emit(self.clock, _trace.EV_FAIL, -1,
                            self.trace_instance)
        orphans = [r for r in self.slots if r is not None] + list(self.queue)
        self.slots = [None] * self.n_slots
        self.queue.clear()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        for r in orphans:
            r.reset_progress()
            r.phase = Phase.QUEUED
            r.instance = None
            # the attempt died: clear timing stamps so TTFT/TBT/E2E
            # measure the attempt that actually serves the request
            r.first_token = None
            r.token_times = []
            r.prefill_done = None
        return orphans

    def recover(self):
        """Undo :meth:`fail`: the instance comes back empty (cold KV)
        at its current clock and resumes accepting work."""
        self.failed = False
        if self.trace.enabled:
            self.trace.emit(self.clock, _trace.EV_RECOVER, -1,
                            self.trace_instance)

    def steal(self, req: Request) -> bool:
        """Withdraw a routed request for hedged re-dispatch; returns
        False if it is no longer here (completed this step)."""
        if req in self.queue:
            self.queue.remove(req)
        else:
            slot = next((i for i, r in enumerate(self.slots)
                         if r is req), None)
            if slot is None:
                return False
            self.slots[slot] = None
        req.reset_progress()
        req.phase = Phase.QUEUED
        req.instance = None
        req.first_token = None
        req.token_times = []
        req.prefill_done = None
        return True
