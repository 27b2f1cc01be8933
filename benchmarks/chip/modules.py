"""Programs of a device trace found by the XLA module name of the jitted
function that ran them.

``jax.jit(f)`` names its XLA module ``jit_<f.__name__>``, and the device
trace's line "XLA Modules" shows each run of it as
``jit_<name>(<fingerprint>)``.  The engine's jitted steps are
``prefill``, ``insert`` and ``decode_step``, so a decode step is found
whatever kernels it runs (``devtrace.programs`` finds programs by the
Pallas kernel inside them, which a model without that kernel never
calls).
"""
from __future__ import annotations

from typing import List

import devtrace as trace_lib


def module_name(event_name: str) -> str:
    """"jit_decode_step(1234)" -> "jit_decode_step"."""
    return event_name.split("(", 1)[0]


def programs(tr: trace_lib.Trace, dev: str, fn: str
             ) -> List[trace_lib.Span]:
    """The runs on ``dev`` inside the window of the jitted function
    ``fn``."""
    lo, hi = tr.window
    want = "jit_" + fn
    return [m for m in trace_lib.overlapping(tr.modules.get(dev, []), lo, hi)
            if module_name(m[0]) == want]


def seconds(tr: trace_lib.Trace, devices: List[str], fn: str) -> float:
    """Device seconds of ``fn``'s runs, summed over ``devices``."""
    return sum(e - s for d in devices for _, s, e in programs(tr, d, fn)
               ) * 1e-9
