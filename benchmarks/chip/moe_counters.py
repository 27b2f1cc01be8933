"""The engines' routing counters, as the per-layer readers use them.

An MoE engine keeps running sums over its decode steps
(``LLMInstance.moe_steps``, ``moe_held_pairs``, ``moe_experts_hit``);
every row of a step counts, free slots too.  A program whose engines
keep no such sums gives nothing.
"""
from __future__ import annotations

from typing import Optional, Tuple


def per_step(rec) -> Optional[Tuple[float, float]]:
    """(pairs that reached a held expert per row, held experts reached
    per step), each summed over the MoE layers, over every decode step
    of ``rec``'s engines."""
    engines = getattr(rec, "engines", None) or []
    steps = sum(getattr(e, "moe_steps", 0) for e in engines)
    if not steps:
        return None
    rows = sum(e.moe_steps * e.n_slots for e in engines)
    return (sum(e.moe_held_pairs for e in engines) / rows,
            sum(e.moe_experts_hit for e in engines) / steps)
