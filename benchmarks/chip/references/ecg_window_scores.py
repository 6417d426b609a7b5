"""Plain reference for the fleet: BayeSlope's window scores in float64,
and tolerance-matched R peaks against the records' true peaks.

Written from the algorithm (paper §IV-B, after BayeSlope), not from the
program: stages 1–2 are the slope product |x'[t]|·|x'[t+1]| (edge samples
repeated), a 25-tap causal moving average, and a generalized logistic
around the window's mean, 1 / (1 + exp(1 − z)).  The program computes the
same in a posit format with every operation rounded; the gap to this
float64 answer is the format's rounding and nothing else.
"""
from __future__ import annotations

import bisect
from typing import Sequence, Tuple

import numpy as np

K_INTEGRATION = 25


def window_scores(windows: np.ndarray) -> np.ndarray:
    """(..., n) raw windows → (..., n) scores in [0, 1], float64."""
    x = np.asarray(windows, np.float64)
    n = x.shape[-1]
    d = np.abs(np.diff(x, axis=-1))
    enh = d[..., :-1] * d[..., 1:]
    enh = np.concatenate([enh[..., :1], enh, enh[..., -1:]], axis=-1)
    csum = np.cumsum(enh / K_INTEGRATION, axis=-1)
    acc = csum.copy()
    acc[..., K_INTEGRATION:] -= csum[..., :n - K_INTEGRATION]
    mu = acc.mean(axis=-1, keepdims=True)
    z = acc / np.maximum(mu, 1e-12)
    return 1.0 / (1.0 + np.exp(np.clip(1.0 - z, -30.0, 30.0)))


def match_peaks(pred: Sequence[int], true: Sequence[int], tol: int
                ) -> Tuple[int, int, int]:
    """Greedy one-to-one matching within ±``tol`` samples, predictions in
    ascending order, each to the nearest unused true peak.  Returns
    (true positives, false positives, false negatives)."""
    t = sorted(int(v) for v in true)
    used = [False] * len(t)
    tp = 0
    for p in sorted(int(v) for v in pred):
        i = bisect.bisect_left(t, p)
        best, bestd = -1, tol + 1
        j = i - 1
        while j >= 0 and p - t[j] <= tol:
            if not used[j]:
                if p - t[j] < bestd:
                    best, bestd = j, p - t[j]
                break
            j -= 1
        j = i
        while j < len(t) and t[j] - p <= tol:
            if not used[j]:
                if t[j] - p < bestd:
                    best, bestd = j, t[j] - p
                break
            j += 1
        if best >= 0:
            used[best] = True
            tp += 1
    return tp, len(pred) - tp, len(t) - tp


def miss_share(tp: int, fp: int, fn: int) -> float:
    """1 − F1 of a pooled match; 0 when there was nothing to find."""
    if tp + fp + fn == 0:
        return 0.0
    return 1.0 - 2.0 * tp / (2.0 * tp + fp + fn)
