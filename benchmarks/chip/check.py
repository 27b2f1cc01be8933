"""What decides ``correct``: the served requests against the plain
reference.

Two numbers are compared, each against its limit:

  served_faults  requests completed in the window that were not served
                 exactly once, on a replica of the cell, with exactly
                 their ``decode_tokens`` ids, each inside the vocabulary
                 (limit 0)
  logit_gap      over a sample of those requests, drawn from the seed and
                 holding each replica's longest, the widest gap by which
                 a served token's reference logit lies below the
                 reference's best logit at that position, as a share of
                 the largest |reference logit| of that request

The reference runs the float32 forward over prompt + served tokens on
the chip that served them, after the program's state is freed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

# a replica's sample: its longest request, then others drawn from the
# seed until SAMPLE_MIN requests and SAMPLE_TOKENS served ids (or all
# it finished in the window)
SAMPLE_MIN = 10
SAMPLE_TOKENS = 400

# logit_gap limit, from the readings in PERF.md section 2: sound runs of
# the program read at most 0.0209, the fp8 control at least 0.1212
LOGIT_GAP_LIMIT = 0.06


@dataclass
class Served:
    """What a request was served, copied off the program's request."""
    rid: int
    replica: int
    prompt: List[int]
    output: List[int]
    decode_tokens: int


def served_faults(served: Sequence[Served], done_counts: Dict[int, int],
                  replicas: int, vocab: int) -> int:
    bad = 0
    for s in served:
        ok = (done_counts.get(s.rid, 0) == 1
              and s.replica in range(replicas)
              and len(s.output) == s.decode_tokens
              and all(0 <= t < vocab for t in s.output))
        bad += not ok
    return bad


def sample(served: Sequence[Served], seed: int, replicas: int
           ) -> List[Served]:
    """Per replica: its longest request, then others drawn from
    ``seed`` until SAMPLE_MIN requests and SAMPLE_TOKENS served ids."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(replicas):
        mine = sorted((s for s in served if s.replica == i),
                      key=lambda s: (len(s.prompt) + len(s.output), s.rid))
        if not mine:
            continue
        pick = [mine.pop()]
        order = rng.permutation(len(mine))
        for j in order:
            if (len(pick) >= SAMPLE_MIN and sum(
                    len(s.output) for s in pick) >= SAMPLE_TOKENS):
                break
            pick.append(mine[j])
        out.extend(pick)
    return out


def gap(ref_logits: np.ndarray, tokens: Sequence[int]) -> float:
    """Widest gap of ``tokens`` below the reference's best, over the
    largest |logit| (rows of ``ref_logits`` predict ``tokens``)."""
    ids = np.asarray(tokens)
    rows = np.arange(ids.size)
    worst = float((ref_logits.max(-1) - ref_logits[rows, ids]).max())
    return worst / float(np.abs(ref_logits).max())


def served_rows(s: Served) -> np.ndarray:
    """The sequence the reference reads: prompt + served ids but the
    last; row ``len(prompt) - 1 + j`` predicts ``output[j]``."""
    return np.asarray(list(s.prompt) + list(s.output[:-1]), np.int32)


def logit_gaps(ref, weights_on, cfg: dict, picks: Sequence[Served],
               length: int, control_on=None):
    """The reference's gap of each pick's served ids, on the replica's
    device (``weights_on(replica)`` gives its float32 weights).  With
    ``control_on(replica)`` (the control's weights) also the gap of the
    token the control puts first at each of the same positions.
    Returns (gaps, control gaps)."""
    gaps, control = [], []
    for s in picks:
        rows = served_rows(s)
        lg = ref.logits(weights_on(s.replica), cfg, rows, length)
        lg = lg[len(s.prompt) - 1:]
        gaps.append(gap(lg, s.output))
        if control_on is not None:
            lc = ref.logits(control_on(s.replica), cfg, rows, length)
            control.append(gap(lg, lc[len(s.prompt) - 1:].argmax(-1)))
    return gaps, control
