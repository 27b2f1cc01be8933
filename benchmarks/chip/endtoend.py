"""End-to-end metrics of one window, from a ``replay.Recorder``.

Every number is taken on the wall clock, over all the work of the
window:

  tokens_per_s  generated tokens that became visible in an advance that
                returned inside the window, over the window's seconds
  ttft_p90_ms   admission to the advance in which the first token
                became visible, over the requests completed in the window
  itl_p95_ms    the gap between successive tokens of a request as the
                gateway sees them (0 for tokens that appear in one
                advance), over all gaps of the requests completed in the
                window: some thousands of gaps, so the tail is steady
  e2e_p90_ms    admission to the last token, over the requests
                completed in the window

The counts and medians beside them go to the run's ``info``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


def compute(rec) -> Dict[str, float]:
    """The window's end-to-end numbers and the counts beside them."""
    end = rec.adv_end
    tokens = sum(1 for stamps in rec.token_adv.values()
                 for k in stamps if rec.in_window(k))
    done = rec.completed_in_window()
    ttft, e2e, tpot, gaps = [], [], [], []
    for rid in done:
        s = rec.token_adv[rid]
        t_adm = rec.admitted[rid]
        ttft.append(end[s[0]] - t_adm)
        e2e.append(end[s[-1]] - t_adm)
        if len(s) > 1:
            tpot.append((end[s[-1]] - end[s[0]]) / (len(s) - 1))
            gaps.extend(end[b] - end[a] for a, b in zip(s, s[1:]))
    if not done:
        raise RuntimeError("no request completed inside the window")
    ks = [k for k in range(rec.open_adv, len(end)) if rec.in_window(k)]
    gw = rec.adv_t[ks[-1]] - rec.adv_t[rec.open_adv - 1]
    out = {
        "tokens_per_s": tokens / rec.seconds,
        "ttft_p90_ms": 1e3 * pct(ttft, 90),
        "itl_p95_ms": 1e3 * pct(gaps, 95) if gaps else 0.0,
        "e2e_p90_ms": 1e3 * pct(e2e, 90),
        # the counts and medians beside them
        "tokens": tokens,
        "completed": len(done),
        "admitted": sum(1 for t in rec.admitted.values()
                        if rec.t_open < t <= rec.t_close),
        "ttft_p50_ms": 1e3 * pct(ttft, 50),
        "tpot_p90_ms": 1e3 * pct(tpot, 90),
        "e2e_p50_ms": 1e3 * pct(e2e, 50),
        "itl_p50_ms": 1e3 * pct(gaps, 50) if gaps else 0.0,
        "tpot_p50_ms": 1e3 * pct(tpot, 50),
        "advances": len(ks),
        "tick_mean_ms": 1e3 * float(np.mean(
            [end[k] - rec.adv_start[k] for k in ks])),
        "resident_mean": float(np.mean([rec.adv_resident[k] for k in ks])),
        "gateway_s": gw,
        "wall_per_gateway_s": rec.seconds / gw if gw > 0 else 0.0,
        "tokens_taken_back": rec.resets,
    }
    return out
