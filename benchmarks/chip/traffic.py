"""Request streams for the chip benchmark, drawn from a traffic file.

A traffic file (``traffic/<name>.json``) is data only: task weights with
lognormal prompt and decode lengths, their clips, the grid that prompt
lengths are snapped to, the arrival process and its rate per replica on
the gateway's clock, the warm-up before the window opens and the horizon
of the stream.  This one generator reads every such file.

The lengths and the inter-arrival gaps are drawn once, from the file's
``sizes_seed``: every ``--seed`` serves the same multiset of requests.
``--seed`` shuffles lengths and gaps within consecutive blocks of
``shuffle_block`` requests and draws the prompt ids, so each seed sends
the same work in another order and a window sees nearly the same load.

The length model and ``arrival_times`` are copied from the program's
``core/workload.py`` (``_SPEC``/``generate``, ``arrival_times``), so that
a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Draw:
    """One request of a stream, before the program sees it."""
    arrival: float          # gateway seconds
    prompt: int             # prompt tokens (on the grid)
    decode: int             # tokens to generate
    task: str
    tokens: np.ndarray      # prompt ids, int32


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def arrival_times(n: int, rate: float, pattern: str, rng,
                  burst_factor: float = 6.0,
                  burst_persistence: float = 0.96) -> np.ndarray:
    """Arrival times of ``n`` requests at mean rate ``rate`` req/s
    (copied from the program's ``core/workload.arrival_times``).

    poisson -- homogeneous Poisson.
    bursty  -- two-state Markov-modulated Poisson: an ON state at
               ``burst_factor`` x the base intensity and a quiet OFF
               state, the state re-drawn per arrival with
               ``burst_persistence``; the long-run rate stays ``rate``.
    """
    if pattern == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=n))
    if pattern == "bursty":
        r_on = burst_factor * rate
        r_off = burst_factor * rate / (2.0 * burst_factor - 1.0)
        out = np.empty(n)
        t, on = 0.0, bool(rng.random() < 0.5)
        for i in range(n):
            t += rng.exponential(1.0 / (r_on if on else r_off))
            out[i] = t
            if rng.random() > burst_persistence:
                on = not on
        return out
    raise ValueError(f"unknown arrival pattern: {pattern}")


def draw_lengths(spec: dict, n: int, rng):
    """Task, prompt and decode lengths of ``n`` requests: the task by
    weight, the prompt lognormal, the decode lognormal with a latent
    factor and a weak coupling to the prompt length (the program's
    ``workload.generate``).  Returns (tasks, prompts, decodes)."""
    tasks = spec["tasks"]
    w = np.array([t["weight"] for t in tasks], float)
    pick = rng.choice(len(tasks), size=n, p=w / w.sum())
    pmu = np.array([tasks[k]["prompt"][0] for k in pick])
    psig = np.array([tasks[k]["prompt"][1] for k in pick])
    dmu = np.array([tasks[k]["decode"][0] for k in pick])
    dsig = np.array([tasks[k]["decode"][1] for k in pick])
    lo, hi = spec["prompt_clip"]
    prompts = np.clip(rng.lognormal(pmu, psig), lo, hi).astype(np.int64)
    latent = rng.normal(0.0, spec.get("decode_latent_sigma", 0.0), n)
    z = rng.normal(size=n)
    log_d = (dmu + dsig * (spec.get("decode_noise", 1.0) * z + latent)
             + spec.get("decode_prompt_coupling", 0.0)
             * np.log(np.maximum(prompts, 1)))
    dlo, dhi = spec["decode_clip"]
    decodes = np.clip(np.exp(log_d), dlo, dhi).astype(np.int64)
    return [tasks[k]["name"] for k in pick], prompts, decodes


def snap(prompts: np.ndarray, grid) -> np.ndarray:
    """Each length to the grid point nearest to it in log space."""
    g = np.asarray(sorted(grid), float)
    idx = np.abs(np.log(np.maximum(prompts, 1))[:, None]
                 - np.log(g)[None, :]).argmin(axis=1)
    return g[idx].astype(np.int64)


def stream(spec: dict, *, seed: int, replicas: int, cache_len: int,
           vocab: int) -> List[Draw]:
    """The cell's stream for ``seed``: arrivals cover ``horizon_s``
    gateway seconds at ``rate_per_replica * replicas``; every request
    fits ``cache_len`` (prompt + decode, decode clipped)."""
    rate = spec["rate_per_replica"] * replicas
    block = int(spec["shuffle_block"])
    n = block * math.ceil(1.1 * spec["horizon_s"] * rate / block)
    fixed = np.random.default_rng(spec["sizes_seed"])
    tasks, prompts, decodes = draw_lengths(spec, n, fixed)
    prompts = snap(prompts, spec["prompt_grid"])
    if prompts.max() >= cache_len:
        raise ValueError(f"prompt grid reaches {prompts.max()} tokens, "
                         f"past cache_len {cache_len}")
    decodes = np.maximum(np.minimum(decodes, cache_len - prompts), 1)
    gaps = np.diff(arrival_times(n, rate, spec["arrivals"], fixed,
                                 **spec.get("arrival_kw", {})),
                   prepend=0.0)
    rng = np.random.default_rng(seed)
    order = np.concatenate([lo + rng.permutation(min(block, n - lo))
                            for lo in range(0, n, block)])
    gap_order = np.concatenate([lo + rng.permutation(min(block, n - lo))
                                for lo in range(0, n, block)])
    arrivals = np.cumsum(gaps[gap_order])
    out = []
    for i, k in enumerate(order):
        p = int(prompts[k])
        out.append(Draw(arrival=float(arrivals[i]), prompt=p,
                        decode=int(decodes[k]), task=tasks[k],
                        tokens=rng.integers(0, vocab, p, dtype=np.int32)))
    return out
