"""Device-resident jitted round loop over the vecsim SoA layout.

``JaxSimPool`` subclasses :class:`repro.core.vecsim.VecSimPool` and
overrides exactly one method — ``_run_rounds`` — the single choke
point both per-tick ``advance`` and the batched trainer's
``advance_span`` flow through.  Everything else (episode/lane
management, the request arena, queue rings, fail/recover/steal,
``VecCluster`` views, trace draining) is inherited unchanged, so all
host-side reads between spans (featurize, policy scores, masks) hit
the same synced numpy arrays and decision parity with the numpy
backend holds by construction.

The override stages the pool's live state onto the device once per
``_run_rounds`` call, runs the WHOLE round sequence inside a single
jitted ``lax.while_loop`` (masked admission, chunked-prefill progress,
gang decode, spike detection, newest-first preemption, backlog/reward
bucketing — a line-for-line transliteration of
``VecSimPool._iterate``), and syncs the results back.  Per-span cost
becomes one device dispatch instead of O(rounds) numpy passes.

Parity contract (gated by ``tests/test_jaxsim.py`` and
``benchmarks/bench_jaxsim.py``):

  * decisions, clocks, TTFT, preemptions, per-request token counts:
    **bit-exact** vs the numpy vecsim (which is itself bit-exact vs
    the Python stepper).  Everything decision-relevant is integer
    arithmetic or identically-associated float expressions.
  * rewards (the backlog S/T accumulators and span bucket sums):
    equal up to float SUMMATION ORDER — the jitted loop reduces
    per-lane/episode contributions with ``segment_sum`` where the
    numpy path runs sequential ``np.add.at`` element loops.  This is
    the SAME documented tolerance class as the existing py-vs-vec
    contract (see vecsim's module docstring); tests assert rewards to
    1e-9 relative.
  * spike VALUES are not materialized on the device path (counts are
    — every consumer in the repo counts ``len(spikes)``); the host
    lists are padded with ``nan`` placeholders per detected spike.

Graceful fallback: lifecycle tracing, prefix-cache admission (per-lane
radix-tree walks are inherently host-side), spans longer than
``SPAN_BUCKETS-1`` ticks, and sub-``min_span_ticks`` spans (dispatch
overhead would dominate) all route to the inherited numpy
``_run_rounds`` — bit-identical results either way, so mixing paths
within one episode is safe.

Arena compaction: the request arena grows monotonically (thousands of
rows over a training run) while only queued+resident requests are
touchable by a round.  Each call gathers those candidate rows into a
compact ``[C_pad]`` block (power-of-two padded to bound retraces),
remaps gids, and scatters results back — device transfer stays
proportional to live requests, not arena capacity.  Masked arena
writes use an out-of-bounds sink index with ``mode='drop'``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ops import segment_sum

from repro.core.vecsim import (
    PH_DONE, PH_PREEMPTED, PH_PREFILL, SCHED_BIN, SCHED_FCFS,
    SS_DECODE, SS_EMPTY, SS_PREFILL, VecSimPool, _BIG,
)

# d_lane reward-bucket columns: col 0 is the discard bucket (lanes
# outside any span clip there), cols 1..SPAN_BUCKETS-1 are span ticks.
# Matches RoutingEnv._span_bounds(cap=256) and the bench drivers'
# SPAN_CAP=256 — longer spans fall back to the numpy path.
SPAN_BUCKETS = 257


def _round_body(ro, c):
    """One fused engine iteration on every active lane — the jitted
    transliteration of ``VecSimPool._iterate`` (same phase order:
    admission, prefill, clock/spike, gang decode + backlog, completion,
    preemption, dry-lane jump)."""
    L, S = c["res_gid"].shape
    Q = c["q"].shape[1]
    C_pad = ro["prompt"].shape[0]
    E = c["bk_t"].shape[0]
    iota_l = jnp.arange(L)
    iota_s = jnp.arange(S)
    iota_q = jnp.arange(Q)

    active = c["active"]
    clock0 = c["clock"]
    rts0 = c["rts"]
    res_gid, st = c["res_gid"], c["s_state"]
    s_prompt, s_dtotal = c["s_prompt"], c["s_dtotal"]
    s_prefilled, s_decoded = c["s_prefilled"], c["s_decoded"]
    s_admit, s_first = c["s_admit"], c["s_first"]
    s_pfdone, s_invd = c["s_pfdone"], c["s_invd"]
    s_invt, s_capat = c["s_invt"], c["s_capat"]
    q, qcnt = c["q"], c["qcnt"]
    res_cnt, pref_cnt = c["res_cnt"], c["pref_cnt"]
    qps, outst = c["qps"], c["outst"]
    lane_ivv, bk_s, bk_t = c["lane_ivv"], c["bk_s"], c["bk_t"]
    d_lane = c["d_lane"]
    prefilled_c, decoded_c = c["a_prefilled"], c["a_decoded"]
    admit_seq_c, phase_c = c["a_admit_seq"], c["a_phase"]
    preempts_c, first_tok_c = c["a_preempts"], c["a_first_tok"]
    prefill_done_c, finished_c = c["a_prefill_done"], c["a_finished"]
    nemit_c = c["a_nemit"]

    # -- admission: one request per lane if a slot is free ------------
    can = active & (res_cnt < ro["nslots"]) & (qcnt > 0)
    budget = ro["cap"] - rts0
    valid = iota_q[None, :] < qcnt[:, None]
    gq_safe = jnp.where(valid, q, 0)
    # queue invariant: queued progress is zero (preemption resets
    # before requeue), so decoded_c adds exactly 0 on the fcfs path too
    adm_cost = ro["prompt"][gq_safe] + decoded_c[gq_safe]
    fit = valid & (adm_cost <= budget[:, None])
    any_fit = fit.any(1)
    pick_fcfs = jnp.where(fit[:, 0], 0, -1)
    size = jnp.where(fit, ro["prompt"][gq_safe] + ro["dtotal"][gq_safe],
                     -1)
    pick_bin = jnp.where(any_fit, jnp.argmax(size, 1), -1)  # first max
    work = jnp.where(fit, ro["dtotal"][gq_safe], _BIG)
    pick_lwl = jnp.where(any_fit, jnp.argmin(work, 1), -1)  # first min
    pick = jnp.where(ro["sched"] == SCHED_FCFS, pick_fcfs,
                     jnp.where(ro["sched"] == SCHED_BIN, pick_bin,
                               pick_lwl))
    admit = can & (pick >= 0)
    pick_s = jnp.maximum(pick, 0)
    gid_adm = jnp.take_along_axis(q, pick_s[:, None], 1)[:, 0]
    gid_safe = jnp.where(admit, gid_adm, 0)
    g_adm = jnp.where(admit, gid_adm, C_pad)           # drop sink
    # logical-order removal of the picked position
    keep = jnp.minimum(iota_q[None, :]
                       + (iota_q[None, :] >= pick_s[:, None]), Q - 1)
    q = jnp.where(admit[:, None], jnp.take_along_axis(q, keep, 1), q)
    qcnt = qcnt - admit
    qps = qps - ro["prompt"][gid_safe] * admit
    seq = c["admit_ctr"]
    admit_seq_c = admit_seq_c.at[g_adm].set(seq, mode="drop")
    admit_ctr = c["admit_ctr"] + admit
    phase_c = phase_c.at[g_adm].set(PH_PREFILL, mode="drop")
    # first-fit slot insert (S is preconditioned host-side, so an
    # admitting lane always has a free column)
    col = jnp.argmax(res_gid == -1, axis=1)

    def _ins(m, val):
        return m.at[iota_l, col].set(
            jnp.where(admit, val, m[iota_l, col]))

    res_gid = _ins(res_gid, gid_adm)
    st = _ins(st, jnp.full(L, SS_PREFILL, st.dtype))
    s_prompt = _ins(s_prompt, ro["prompt"][gid_safe])
    s_dtotal = _ins(s_dtotal, ro["dtotal"][gid_safe])
    s_prefilled = _ins(s_prefilled, prefilled_c[gid_safe])
    s_decoded = _ins(s_decoded, decoded_c[gid_safe])
    s_admit = _ins(s_admit, seq)
    s_first = _ins(s_first, first_tok_c[gid_safe])
    s_pfdone = _ins(s_pfdone, prefill_done_c[gid_safe])
    s_invd = _ins(s_invd, ro["inv_d"][gid_safe])
    s_invt = _ins(s_invt, ro["inv_t"][gid_safe])
    s_capat = _ins(s_capat, ro["capat"][gid_safe])
    res_cnt = res_cnt + admit
    pref_cnt = pref_cnt + admit
    # (no prefix cache on this path: admitted progress is exactly 0,
    # so the scalar stepper's rts/outst adjustment is a no-op)

    act2 = active[:, None]
    # -- prefill progress (full, or one chunk per iteration) ----------
    pref = (st == SS_PREFILL) & act2
    rem = (s_prompt - s_prefilled) * pref
    step = jnp.minimum(ro["chunk"][:, None], rem) * pref
    # unchunked lanes: only the FIRST (by admission order) prefilling
    # resident runs, for its full remaining prompt
    aseq = s_admit + (~pref) * _BIG
    firstc = jnp.argmin(aseq, 1)
    ustep = jnp.zeros_like(step).at[iota_l, firstc].set(
        jnp.where(pref.any(1), rem[iota_l, firstc], 0))
    step = jnp.where((ro["chunk"] == 0)[:, None], ustep, step)
    s_prefilled = s_prefilled + step
    prefill_tokens = step.sum(1)
    fin_pref = pref & (s_prefilled >= s_prompt)
    st = jnp.where(fin_pref, SS_DECODE, st)
    s_pfdone = jnp.where(fin_pref, clock0[:, None], s_pfdone)
    pref_cnt = pref_cnt - fin_pref.sum(1)
    lane_ivv = lane_ivv + (s_invd * s_invt * fin_pref).sum(1)
    outst = outst - prefill_tokens

    # -- iteration time + spikes (same association order as the numpy
    # -- expression, so nominal/zero-tpre lanes stay bit-identical).
    # XLA:CPU unconditionally lets the LLVM backend contract mul+add
    # into FMA (TargetOptions AllowFPOpFusion=Fast, no flag), which
    # rounds once where numpy rounds twice and drifts the clock by
    # 1 ulp.  Adding the RUNTIME zero ``ro["fp_zero"]`` after each
    # product forces the rounding boundary: the compiler cannot fold
    # ``x + z`` (z is a parameter), and any fma it forms around the
    # zero term is exact.  All terms are non-negative, so +0.0 cannot
    # flip a signed zero. -----------------------------------------------
    z = ro["fp_zero"]
    it_time = (ro["tdec"] + (ro["grad1"] * prefill_tokens + z)
               + (ro["grad2"] * rts0 + z)
               + (ro["tpre"] * (prefill_tokens > 0) + z)
               ) * ro["speed"] + z
    spike = active & (it_time > 2.0 * ro["tdec"] * ro["speed"])
    spike_cnt = c["spike_cnt"] + spike
    clock1 = clock0 + it_time
    clock = jnp.where(active, clock1, clock0)
    rts1 = rts0 + prefill_tokens

    # -- gang decode + backlog T accrual ------------------------------
    dec = (st == SS_DECODE) & act2
    per_lane = dec.sum(1)
    s_decoded = s_decoded + dec
    fresh = dec & jnp.isnan(s_first)
    s_first = jnp.where(fresh, clock1[:, None], s_first)
    rts2 = rts1 + per_lane
    outst = outst - per_lane
    delta = lane_ivv * active
    bk_t = bk_t + segment_sum(delta, ro["lane_ep"], num_segments=E)
    # span reward bucket for every contribution whose iteration starts
    # at clock0; lanes outside a span clip to the discard column 0
    b_all = jnp.clip(
        jnp.floor((clock0 - ro["span_t0"])
                  / ro["ep_dt_lane"]).astype(jnp.int64) + 1,
        1, ro["lane_k"])
    d_lane = d_lane.at[iota_l, b_all].add(delta)
    crossed = dec & (s_decoded == s_capat)
    full_tok = s_invd * s_invt
    part = (1.0 - (s_capat - 1) * s_invd) * s_invt
    corr_lane = ((part - full_tok) * crossed).sum(1)
    bk_t = bk_t + segment_sum(corr_lane, ro["lane_ep"], num_segments=E)
    lane_ivv = lane_ivv - (full_tok * crossed).sum(1)
    d_lane = d_lane.at[iota_l, b_all].add(corr_lane)

    # -- completions --------------------------------------------------
    fin = dec & (s_decoded >= s_dtotal)
    g_fin = jnp.where(fin, res_gid, C_pad)
    phase_c = phase_c.at[g_fin].set(PH_DONE, mode="drop")
    finished_c = finished_c.at[g_fin].set(
        jnp.broadcast_to(clock1[:, None], (L, S)), mode="drop")
    prefilled_c = prefilled_c.at[g_fin].set(s_prefilled, mode="drop")
    decoded_c = decoded_c.at[g_fin].set(s_decoded, mode="drop")
    first_tok_c = first_tok_c.at[g_fin].set(s_first, mode="drop")
    nemit_c = nemit_c.at[g_fin].add(s_decoded, mode="drop")
    prefill_done_c = prefill_done_c.at[g_fin].set(s_pfdone, mode="drop")
    done_round = c["done_round"].at[g_fin].set(c["round_no"],
                                               mode="drop")
    done_col = c["done_col"].at[g_fin].set(
        jnp.broadcast_to(iota_s[None, :], (L, S)), mode="drop")
    drop_sum = ((s_prefilled + s_decoded) * fin).sum(1)
    rts = jnp.where(active, rts2 - drop_sum, rts0)
    res_cnt = res_cnt - fin.sum(1)
    # backlog settle: T -= progress, S -= inv_t, bucketed at the tick
    # the final iteration started; uncapped finishers leave lane_ivv
    prog = jnp.minimum(s_decoded * s_invd, 1.0) * s_invt
    bk_s = bk_s - segment_sum((s_invt * fin).sum(1), ro["lane_ep"],
                              num_segments=E)
    bk_t = bk_t - segment_sum((prog * fin).sum(1), ro["lane_ep"],
                              num_segments=E)
    d_lane = d_lane.at[iota_l, b_all].add(((s_invt - prog) * fin).sum(1))
    uncap = fin & (s_decoded < s_capat)
    lane_ivv = lane_ivv - (s_invd * s_invt * uncap).sum(1)
    res_gid = jnp.where(fin, -1, res_gid)
    st = jnp.where(fin, SS_EMPTY, st)

    # -- capacity enforcement: evict newest-admitted ------------------
    # closed form of the sequential loop: sort residents newest-first
    # (admit seq strictly increases per lane, so no ties), evict the
    # smallest prefix k whose progress sum brings rts within cap,
    # bounded by res_cnt-1 (the oldest resident is never evicted).
    # All quantities integer-valued f64 / int64, so prefix sums match
    # the loop's sequential subtractions bit for bit.
    over = (rts > ro["cap"]) & active & (res_cnt > 1)
    occ = res_gid >= 0
    keys = jnp.where(occ, s_admit, -1)
    order = jnp.argsort(-keys, axis=1)
    g_sorted = jnp.take_along_axis(res_gid, order, 1)
    prog_mat = s_prefilled + s_decoded
    prog_sorted = jnp.take_along_axis(prog_mat, order, 1) \
        * (g_sorted >= 0)
    csum = jnp.cumsum(prog_sorted, 1)
    ok = (rts[:, None] - csum) <= ro["cap"][:, None]
    k_fit = jnp.where(ok.any(1), jnp.argmax(ok, 1) + 1, S)
    k = jnp.where(over, jnp.minimum(k_fit, res_cnt - 1), 0)
    evict_sorted = iota_s[None, :] < k[:, None]
    evict = jnp.zeros((L, S), bool).at[iota_l[:, None], order].set(
        evict_sorted)
    g_ev = jnp.where(evict, res_gid, C_pad)
    # arena write-back then progress reset (net of _evict_slot +
    # _reset_progress; prefill_done is retained across preemption)
    prefilled_c = prefilled_c.at[g_ev].set(0, mode="drop")
    decoded_c = decoded_c.at[g_ev].set(0, mode="drop")
    first_tok_c = first_tok_c.at[g_ev].set(s_first, mode="drop")
    nemit_c = nemit_c.at[g_ev].add(s_decoded, mode="drop")
    prefill_done_c = prefill_done_c.at[g_ev].set(s_pfdone, mode="drop")
    phase_c = phase_c.at[g_ev].set(PH_PREEMPTED, mode="drop")
    preempts_c = preempts_c.at[g_ev].add(1, mode="drop")
    debit_lane = (jnp.minimum(s_decoded * s_invd, 1.0) * s_invt
                  * (evict & (s_decoded > 0))).sum(1)
    bk_t = bk_t - segment_sum(debit_lane, ro["lane_ep"],
                              num_segments=E)
    d_lane = d_lane.at[iota_l, b_all].add(-debit_lane)
    lane_ivv = lane_ivv - (s_invd * s_invt
                           * (evict & (st == SS_DECODE)
                              & (s_decoded < s_capat))).sum(1)
    pref_cnt = pref_cnt - (evict & (st == SS_PREFILL)).sum(1)
    prog_ev = (prog_mat * evict).sum(1)
    rts = rts - prog_ev
    qps = qps + (s_prompt * evict).sum(1)
    outst = outst + prog_ev
    res_cnt = res_cnt - k
    res_gid = jnp.where(evict, -1, res_gid)
    st = jnp.where(evict, SS_EMPTY, st)
    # requeue in ascending admit-seq order at the queue FRONT (the
    # sequential loop pushes-left newest-first, which lands oldest-
    # evicted at the head)
    idx_rev = jnp.clip(k[:, None] - 1 - iota_s[None, :], 0, S - 1)
    ev_asc = jnp.take_along_axis(g_sorted, idx_rev, 1)
    evq = jnp.take_along_axis(
        ev_asc, jnp.broadcast_to(jnp.clip(iota_q, 0, S - 1)[None, :],
                                 (L, Q)), 1)
    tail = jnp.take_along_axis(
        q, jnp.clip(iota_q[None, :] - k[:, None], 0, Q - 1), 1)
    q = jnp.where(k[:, None] > 0,
                  jnp.where(iota_q[None, :] < k[:, None], evq, tail), q)
    qcnt = qcnt + k

    # -- loop bookkeeping ---------------------------------------------
    active = active & (clock < ro["target"])
    dry = active & ~((res_cnt > 0) | (qcnt > 0))
    clock = jnp.where(dry, ro["target"], clock)
    active = active & ~dry

    return dict(
        active=active, clock=clock, rts=rts, qps=qps, outst=outst,
        admit_ctr=admit_ctr, res_cnt=res_cnt, pref_cnt=pref_cnt,
        qcnt=qcnt, q=q, res_gid=res_gid, s_state=st, s_prompt=s_prompt,
        s_dtotal=s_dtotal, s_prefilled=s_prefilled,
        s_decoded=s_decoded, s_admit=s_admit, s_first=s_first,
        s_pfdone=s_pfdone, s_invd=s_invd, s_invt=s_invt,
        s_capat=s_capat, lane_ivv=lane_ivv, spike_cnt=spike_cnt,
        bk_s=bk_s, bk_t=bk_t, d_lane=d_lane, a_prefilled=prefilled_c,
        a_decoded=decoded_c, a_admit_seq=admit_seq_c, a_phase=phase_c,
        a_preempts=preempts_c, a_first_tok=first_tok_c,
        a_prefill_done=prefill_done_c, a_finished=finished_c,
        a_nemit=nemit_c, done_round=done_round, done_col=done_col,
        round_no=c["round_no"] + 1)


@jax.jit
def _run_kernel(ro, carry):
    """All rounds of one ``_run_rounds`` call, on device."""
    return lax.while_loop(lambda c: c["active"].any(),
                          lambda c: _round_body(ro, c), carry)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


class JaxSimPool(VecSimPool):
    """VecSimPool whose round loop runs as one jitted device program.

    Drop-in: ``Cluster(..., backend="jax")`` and
    ``BatchedRLConfig(backend="jax")`` resolve here through the
    ``core.backends`` registry.  ``min_span_ticks`` tunes the hybrid
    dispatch threshold: spans estimated shorter than this many ticks
    run on the inherited numpy path (device dispatch overhead
    dominates 1–2 round spans on CPU XLA); results are identical
    either way."""

    def __init__(self, n_episodes: int = 1, arena_cap: int = 1024,
                 min_span_ticks: int = 8):
        super().__init__(n_episodes, arena_cap)
        self.min_span_ticks = min_span_ticks
        # dispatch instrumentation (bench_jaxsim reports these)
        self.n_jax_calls = 0
        self.n_numpy_calls = 0
        # grow-only compact-arena padding: every distinct (L, S, Q,
        # C_pad) tuple is one XLA compile of the (large) round kernel,
        # so the pad must not track the live candidate count up and
        # down -- it only ratchets, and shapes go static after the
        # first episode
        self._c_pad = 64

    # -- the single override ------------------------------------------
    def _run_rounds(self, target: np.ndarray,
                    done: Dict[int, List[int]]):
        if not self._jax_eligible(target):
            self.n_numpy_calls += 1
            return super()._run_rounds(target, done)
        self._park_failed(target)
        behind = self.clock < target
        if not behind.any():
            return
        runnable = ((self.res_cnt > 0) | (self.qcnt > 0)) & ~self.failed
        jump = behind & ~runnable
        if jump.any():
            self.clock[jump] = target[jump]
        active = behind & runnable
        if not active.any():
            return
        self.n_jax_calls += 1
        self._dispatch(active, target, done)

    def _jax_eligible(self, target) -> bool:
        if self.trace.enabled or self._any_cache or self._L == 0:
            return False
        if self._span is not None:
            lane_k = self._span[2]
            k_max = int(lane_k.max()) if lane_k.size else 0
            if k_max >= SPAN_BUCKETS:
                return False
            return k_max >= self.min_span_ticks
        # per-tick advance: estimate the span length in ticks
        gap = target - self.clock
        behind = gap > 0
        if not behind.any():
            return True            # nothing to do; either path returns
        ticks = gap[behind] / self.ep_dt[self.lane_ep[behind]]
        return float(ticks.max()) >= self.min_span_ticks

    # -- staging / writeback ------------------------------------------
    def _dispatch(self, active: np.ndarray, target: np.ndarray,
                  done: Dict[int, List[int]]):
        L, S, Q = self._L, self._S, self._Q
        # precondition widths so the kernel never needs to grow:
        # residents are bounded by min(nslots, res_cnt+qcnt) (no new
        # submissions inside a span), queues by res_cnt+qcnt (preempt
        # requeues at most res_cnt-1)
        need_s = int(np.minimum(self.nslots,
                                self.res_cnt + self.qcnt).max())
        while self._S < need_s:
            self._grow_res()
        need_q = int((self.res_cnt + self.qcnt).max())
        while self._Q < need_q:
            self._grow_queue()
        S, Q = self._S, self._Q
        # candidate rows: every queued or resident gid, all lanes (the
        # carry holds full-width matrices, so even inactive lanes'
        # gids must survive the remap round trip)
        pos = (self.qhead[:, None] + np.arange(Q)) % Q
        gq = self.q_gid[np.arange(L)[:, None], pos]     # logical order
        qvalid = np.arange(Q) < self.qcnt[:, None]
        cand = np.unique(np.concatenate(
            [self.res_gid[self.res_gid >= 0], gq[qvalid]]))
        C = cand.size
        need = _next_pow2(max(C, 1))
        if need > self._c_pad:
            self._c_pad = need
        C_pad = self._c_pad
        gmap = np.full(self._cap_g, -1, np.int64)
        gmap[cand] = np.arange(C)
        res_gid_c = np.where(self.res_gid >= 0,
                             gmap[np.maximum(self.res_gid, 0)], -1)
        q_c = np.where(qvalid, gmap[np.maximum(gq, 0)], -1)

        def _pad(col):
            out = np.zeros(C_pad, col.dtype)
            out[:C] = col[cand]
            return out

        if self._span is not None:
            span_t0, lane_off, lane_k, _ = self._span
        else:
            span_t0 = np.zeros(L)
            lane_k = np.zeros(L, np.int64)
        ro = dict(
            target=target, cap=self.cap, nslots=self.nslots,
            grad1=self.grad1, grad2=self.grad2, tdec=self.tdec,
            tpre=self.tpre, speed=self.speed, chunk=self.chunk,
            sched=self.sched.astype(np.int64), lane_ep=self.lane_ep,
            ep_dt_lane=self.ep_dt[self.lane_ep], span_t0=span_t0,
            lane_k=lane_k, prompt=_pad(self.prompt),
            dtotal=_pad(self.dtotal), inv_d=_pad(self.inv_d),
            inv_t=_pad(self.inv_t), capat=_pad(self.capat),
            fp_zero=np.float64(0.0))
        carry = dict(
            active=active, clock=self.clock, rts=self.rts,
            qps=self.qps, outst=self.outst, admit_ctr=self.admit_ctr,
            res_cnt=self.res_cnt, pref_cnt=self.pref_cnt,
            qcnt=self.qcnt, q=q_c, res_gid=res_gid_c,
            s_state=self.s_state.astype(np.int64),
            s_prompt=self.s_prompt, s_dtotal=self.s_dtotal,
            s_prefilled=self.s_prefilled, s_decoded=self.s_decoded,
            s_admit=self.s_admit, s_first=self.s_first,
            s_pfdone=self.s_pfdone, s_invd=self.s_invd,
            s_invt=self.s_invt, s_capat=self.s_capat,
            lane_ivv=self.lane_ivv,
            spike_cnt=np.zeros(L, np.int64), bk_s=self.bk_s,
            bk_t=self.bk_t, d_lane=np.zeros((L, SPAN_BUCKETS)),
            a_prefilled=_pad(self.prefilled),
            a_decoded=_pad(self.decoded),
            a_admit_seq=_pad(self.admit_seq), a_phase=_pad(
                self.phase).astype(np.int64),
            a_preempts=_pad(self.preempts),
            a_first_tok=_pad(self.first_tok),
            a_prefill_done=_pad(self.prefill_done),
            a_finished=_pad(self.finished), a_nemit=_pad(self.nemit),
            done_round=np.full(C_pad, -1, np.int64),
            done_col=np.zeros(C_pad, np.int64),
            round_no=np.int64(0))
        with jax.enable_x64(True):
            out = _run_kernel(ro, carry)
        out = {k: np.asarray(v) for k, v in out.items()}
        self._writeback(out, cand, C, done)

    def _writeback(self, out, cand, C, done):
        L, Q = self._L, self._Q
        for name in ("clock", "rts", "qps", "outst", "admit_ctr",
                     "res_cnt", "pref_cnt", "qcnt", "lane_ivv",
                     "bk_s", "bk_t", "s_prompt", "s_dtotal",
                     "s_prefilled", "s_decoded", "s_admit", "s_first",
                     "s_pfdone", "s_invd", "s_invt", "s_capat"):
            getattr(self, name)[...] = out[name]
        self.s_state[...] = out["s_state"].astype(np.int8)
        # un-remap compact gids; queues come back logically ordered
        rc = out["res_gid"]
        self.res_gid[...] = np.where(rc >= 0, cand[np.maximum(rc, 0)],
                                     -1)
        qc = out["q"]
        qvalid = np.arange(Q) < out["qcnt"][:, None]
        self.q_gid[...] = np.where(qvalid & (qc >= 0),
                                   cand[np.maximum(qc, 0)], -1)
        self.qhead[:] = 0
        for src, dst in (("a_prefilled", "prefilled"),
                         ("a_decoded", "decoded"),
                         ("a_admit_seq", "admit_seq"),
                         ("a_preempts", "preempts"),
                         ("a_first_tok", "first_tok"),
                         ("a_prefill_done", "prefill_done"),
                         ("a_finished", "finished"),
                         ("a_nemit", "nemit")):
            getattr(self, dst)[cand] = out[src][:C]
        self.phase[cand] = out["a_phase"][:C].astype(np.int8)
        # python-int gates for the (possibly interleaved) numpy path
        self._tot_q = int(out["qcnt"].sum())
        self._tot_pref = int(out["pref_cnt"].sum())
        self._tot_dec = int((self.s_state == SS_DECODE).sum())
        self._next_fin = 0
        occ = (self.res_gid >= 0).any(0)
        self._hw = (int(np.flatnonzero(occ).max()) + 1 if occ.any()
                    else 0)
        # spikes: counts only (placeholder values; see module doc)
        for lane in np.flatnonzero(out["spike_cnt"]):
            self.spikes[int(lane)].extend(
                [float("nan")] * int(out["spike_cnt"][lane]))
        # span reward buckets: fold per-lane rows into the flat
        # per-episode tick vector (col 0 is the discard bucket)
        if self._span is not None:
            _, lane_off, lane_k, d_flat = self._span
            cols = np.arange(1, SPAN_BUCKETS)
            mask = cols[None, :] <= lane_k[:, None]
            idx = lane_off[:, None] + cols[None, :]
            np.add.at(d_flat, idx[mask], out["d_lane"][:, 1:][mask])
        # completions, replayed in the vec backend's order: round-
        # major, then lane, then slot column within a lane
        new = np.flatnonzero(out["done_round"][:C] >= 0)
        if new.size:
            gids = cand[new]
            order = np.lexsort((out["done_col"][new],
                                self.lane[gids],
                                out["done_round"][new]))
            for j in order:
                gid = int(gids[j])
                self._sync_done(gid)
                done[int(self.lane_ep[self.lane[gid]])].append(gid)
