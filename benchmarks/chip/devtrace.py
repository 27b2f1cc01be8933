"""Reduction of a device trace to what the per-layer metrics read.

``read`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of interval, all on the profiler's one clock, in
nanoseconds:

  ops      per device, the operations that ran on it (line "XLA Ops")
  modules  per device, the programs that ran on it (line "XLA Modules")
  host     the host's spans: the harness's TraceAnnotations ("route",
           "advance") and JAX's own dispatch spans

``Trace.to_json``/``from_json`` keep such a reduction on disk, as the
recorded trace the tests read does.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Span = Tuple[str, float, float]        # (name, start ns, end ns)


@dataclass
class Trace:
    ops: Dict[str, List[Span]] = field(default_factory=dict)
    modules: Dict[str, List[Span]] = field(default_factory=dict)
    host: List[Span] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules,
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda xs: [tuple(x) for x in xs]            # noqa: E731
        return cls(ops={k: tup(v) for k, v in d["ops"].items()},
                   modules={k: tup(v) for k, v in d["modules"].items()},
                   host=tup(d["host"]), window=tuple(d["window"]))


def read(path: str, window: Optional[Tuple[float, float]] = None
         ) -> Trace:
    """The intervals of the xplane file at ``path``.  ``window`` (ns, on
    the trace's clock) defaults to the span of the host's annotations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name[len("/device:"):]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[dev] = [(e.name, e.start_ns, e.end_ns)
                                   for e in line.events]
                elif line.name == "XLA Modules":
                    tr.modules[dev] = [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
    tr.host.sort(key=lambda s: s[1])
    if window is None:
        marks = [s for s in tr.host if s[0] in ("advance", "route")]
        window = ((marks[0][1], max(s[2] for s in marks)) if marks
                  else (0.0, 0.0))
    tr.window = window
    return tr


def union(spans: List[Span], lo: float, hi: float) -> List[Tuple[float,
                                                                  float]]:
    """Merged [start, end] intervals of ``spans`` clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in spans
                if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, dev: str) -> float:
    lo, hi = tr.window
    return sum(e - s for s, e in union(tr.ops.get(dev, []), lo, hi))


def idle_gaps(tr: Trace, dev: str) -> List[Tuple[float, float]]:
    """The intervals of the window in which nothing ran on ``dev``."""
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in union(tr.ops.get(dev, []), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_span_at(tr: Trace, t: float) -> str:
    """The innermost host span open at ``t``, as outer/inner names."""
    names = [n for n, s, e in tr.host if s <= t < e]
    return "/".join(names[-2:]) if names else "gateway"


def overlapping(spans: List[Span], lo: float, hi: float) -> List[Span]:
    """The spans that overlap [lo, hi], whole.  The device's clock is
    mapped onto the host's with an error of a fraction of a millisecond,
    so a program that a traced advance ran can start just before the
    advance's host span does."""
    return [sp for sp in spans if sp[2] > lo and sp[1] < hi]


# The device trace names an operation by its HLO instruction text,
# "%<name>.<n> = <shape> <opcode>(...)"; a Pallas kernel's custom call
# takes the name of the jitted wrapper that calls it.  A program is a
# decode step if it runs the decode-attention kernel and a prefill if it
# runs the flash kernel.
KERNELS = {"decode": "%decode_attention.", "prefill": "%flash_attention."}


def short_name(op_name: str) -> str:
    """"%fusion.12 = bf16[16,1024]{...} fusion(...)" -> "%fusion.12
    fusion bf16[16,1024]": the instruction, its opcode and its shape."""
    head = op_name.split(" = ", 1)
    if len(head) < 2:
        return op_name
    body = head[1]
    if body.startswith("("):                  # a tuple's shape
        depth = 0
        for i, ch in enumerate(body):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "(tuple)", body[i + 1:]
    else:
        shape, _, rest = body.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    return f"{head[0]} {opcode} {shape.split('{', 1)[0]}"


def kernel_kind(op_name: str) -> Optional[str]:
    head = op_name.split(" = ", 1)
    if len(head) < 2 or "custom-call(" not in head[1]:
        return None
    for kind, mark in KERNELS.items():
        if head[0].startswith(mark):
            return kind
    return None


def programs(tr: Trace, dev: str, kind: str) -> List[Span]:
    """The programs on ``dev`` inside the window that run ``kind``'s
    kernel."""
    lo, hi = tr.window
    starts = sorted(s for n, s, _ in overlapping(tr.ops.get(dev, []), lo, hi)
                    if kernel_kind(n) == kind)
    out = []
    for mod in overlapping(tr.modules.get(dev, []), lo, hi):
        i = bisect.bisect_left(starts, mod[1])
        if i < len(starts) and starts[i] < mod[2]:
            out.append(mod)
    return out


def program_seconds(tr: Trace, devices: List[str], kind: str) -> float:
    """Device seconds of ``kind``'s programs, summed over ``devices``."""
    return sum(e - s for d in devices for _, s, e in programs(tr, d, kind)
               ) * 1e-9


def kernel_seconds(tr: Trace, devices: List[str], kind: str) -> float:
    """Device seconds of ``kind``'s kernel calls, summed over
    ``devices``."""
    lo, hi = tr.window
    return sum(e - s for d in devices
               for n, s, e in overlapping(tr.ops.get(d, []), lo, hi)
               if kernel_kind(n) == kind) * 1e-9
