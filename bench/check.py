"""The comparison that decides ``correct``.

Each answer the timed path returned is compared with the plain reference
run on the same input.  The number compared is the widest logit gap:
max |program - reference| over every compared logit, as a share of the
reference logits' RMS, so that it reads the same on every seed whatever
the logits' scale.  A wrong-shaped or non-finite answer reads +inf.
Answers that were admitted but never came are counted apart; their limit
is 0.  Each configuration states its own gap limit
(``check.logit_gap_limit``), set from the readings in ``PERF.md``.
"""
from __future__ import annotations

import math

import numpy as np


def logit_gap(outs: np.ndarray, ref: np.ndarray) -> float:
    outs = np.asarray(outs, np.float64)
    ref = np.asarray(ref, np.float64)
    if outs.shape != ref.shape or outs.size == 0:
        return math.inf
    if not np.all(np.isfinite(outs)):
        return math.inf
    rms = float(np.sqrt(np.mean(ref ** 2))) or 1.0
    return float(np.max(np.abs(outs - ref))) / rms


def judge(limit: float, gap: float, missing: int, compared: int) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the result line."""
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "answers_missing": {"value": missing, "limit": 0},
              "answers_compared": {"value": compared, "limit": 1}}
    correct = gap <= limit and missing == 0 and compared >= 1
    return correct, checks
