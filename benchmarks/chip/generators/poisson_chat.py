"""The serving lane's load generator: chat requests arriving open loop.

A mix is a JSON file of parameters: ``rate_per_s`` (Poisson arrivals),
lognormal ``prompt_tokens`` and ``output_tokens`` (median, sigma, clipped
to [min, max]).  The arrival times and each request's sizes are drawn
from the mix itself, so every seed offers the same work at the same
moments: at the offered load the tail of the time to first token is set
by which long prompts arrive together, and a seed that reordered them
would change the work, not just the inputs.  The seed draws the prompts'
token ids (and, in the driver, the weights).  Nothing here imports JAX.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

MIX_SEED = 20250128          # fixes the set of sizes and gaps of a mix


@dataclasses.dataclass(frozen=True)
class Request:
    arrival_s: float         # from the window's opening
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    v = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests due in a window of ``seconds``, in arrival order."""
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    fixed = np.random.default_rng(MIX_SEED)
    gaps = fixed.exponential(1.0 / traffic["rate_per_s"], n)
    gaps *= seconds / gaps.sum()            # n arrivals span the window
    prompts = _lognormal(fixed, traffic["prompt_tokens"], n)
    outs = _lognormal(fixed, traffic["output_tokens"], n)
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFF)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Request(float(t), rng.integers(1, vocab, int(p)).astype(np.int32),
                    int(o)) for t, p, o in zip(arrivals, prompts, outs)]


def buckets(traffic: dict) -> List[int]:
    """The prompt lengths that reach every power-of-two prefill bucket the
    mix can use: one warm request each."""
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    out, b = [], 1
    while b < hi:
        b *= 2
        if b >= lo:
            out.append(min(b, hi))
    return sorted(set([lo] + out))
