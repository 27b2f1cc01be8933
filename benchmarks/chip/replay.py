"""Wall-clock stamps around the gateway's calls into its cluster and its
router, and the window that closes the run.

``Recorder`` wraps any object with the gateway's Cluster protocol
(``t``, ``enqueue``, ``route``, ``advance``; everything else is passed
through).  It never changes how the cluster steps.  It stamps:

  * the wall time at which each request was admitted (``enqueue``);
  * the start and end of every ``advance``;
  * for each routed request, the advance in which each of its tokens
    became visible (``len(output_tokens)`` grew), and the advance that
    reported it done.

The window opens when an ``advance`` returns with the gateway's clock
at or past ``warmup_s``, and closes ``seconds`` of wall time later: the
first ``advance`` that returns after that raises ``WindowClosed``,
which stops ``Gateway.run``.

``TimedPolicy`` wraps the router the same way and stamps each
``route`` call.  With ``annotate`` both open a
``jax.profiler.TraceAnnotation`` around the call, so that a device
trace can blame idle gaps on them.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional


class WindowClosed(Exception):
    """The measured window is over; the gateway stops here."""


def _span(annotate: bool, name: str):
    if not annotate:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class TimedPolicy:
    def __init__(self, policy, annotate: bool = False):
        self._policy = policy
        self._annotate = annotate
        self.calls: List[tuple] = []          # (wall start, seconds)

    def __getattr__(self, name):
        return getattr(self._policy, name)

    def route(self, cluster, req, d_hat):
        t0 = time.perf_counter()
        with _span(self._annotate, "route"):
            out = self._policy.route(cluster, req, d_hat)
        self.calls.append((t0, time.perf_counter() - t0))
        return out


class Recorder:
    def __init__(self, cluster, warmup_s: float, seconds: float,
                 annotate: bool = False,
                 on_advance: Optional[Callable] = None):
        self._cluster = cluster
        self.warmup_s = warmup_s
        self.seconds = seconds
        self._annotate = annotate
        self._on_advance = on_advance
        self.admitted: Dict[int, float] = {}      # rid -> wall
        self.requests: Dict[int, object] = {}     # rid -> Request
        self.token_adv: Dict[int, List[int]] = {}  # rid -> advance per token
        self.routed: List[int] = []               # rids, in routing order
        self.done_adv: Dict[int, List[int]] = {}  # rid -> advances done in
        self.adv_start: List[float] = []
        self.adv_end: List[float] = []
        self.adv_t: List[float] = []              # gateway clock after
        self.adv_resident: List[int] = []         # slots in use after
        self.resets = 0                           # tokens taken back
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.open_adv: Optional[int] = None       # first advance inside
        self._live: Dict[int, object] = {}

    def __getattr__(self, name):
        return getattr(self._cluster, name)

    def enqueue(self, req):
        self.admitted.setdefault(req.rid, time.perf_counter())
        self.requests[req.rid] = req
        self.token_adv.setdefault(req.rid, [])
        self._cluster.enqueue(req)

    def route(self, idx):
        req = self._cluster.route(idx)
        self.routed.append(req.rid)
        self._live[req.rid] = req
        return req

    def advance(self):
        t0 = time.perf_counter()
        with _span(self._annotate, "advance"):
            done = self._cluster.advance()
        t1 = time.perf_counter()
        k = len(self.adv_end)
        self.adv_start.append(t0)
        self.adv_end.append(t1)
        self.adv_t.append(self._cluster.t)
        for rid, req in list(self._live.items()):
            stamps = self.token_adv[rid]
            n = len(req.output_tokens)
            if n < len(stamps):              # preempted: progress reset
                self.resets += len(stamps) - n
                del stamps[n:]
            stamps.extend([k] * (n - len(stamps)))
        for req in done:
            self._live.pop(req.rid, None)
            self.done_adv.setdefault(req.rid, []).append(k)
        self.adv_resident.append(sum(
            1 for r in self._live.values() if r.output_tokens))
        closing = self.t_open is not None and \
            t1 >= self.t_open + self.seconds
        if self.t_open is None and self._cluster.t >= self.warmup_s:
            self.t_open = t1
            self.open_adv = k + 1
        if self._on_advance is not None:
            self._on_advance(self, t1)
        if closing:
            self.t_close = self.t_open + self.seconds
            raise WindowClosed()
        return done

    # -- what the window holds ------------------------------------------
    def in_window(self, k: int) -> bool:
        """Advance ``k`` returned inside the window."""
        return (self.open_adv is not None and k >= self.open_adv
                and self.adv_end[k] <= self.t_close)

    def completed_in_window(self) -> List[int]:
        """Rids the cluster first reported done inside the window."""
        return [rid for rid, ks in self.done_adv.items()
                if self.in_window(ks[0])]
