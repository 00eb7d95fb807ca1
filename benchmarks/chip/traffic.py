"""Traffic generation: prompts and arrival times, drawn from the seed.

One general generator reads every mix under ``traffic/``.  A mix is a
JSON object:

* ``loop``: ``"closed"`` (one batch of ``batch`` requests in flight; the
  next is submitted when the previous one has completed) or ``"open"``
  (requests due on a Poisson schedule at ``rate_per_s``, whatever
  the system is doing, served ``max_batch`` at a time);
* ``prompt_len``, ``new_tokens``: fixed lengths, greedy sampling.

Prompts come from ``--seed`` through ``numpy.random.SeedSequence``,
which takes any whole number, so two seeds never share a stream.  An
open loop's due times come from the mix's own ``schedule_seed``: the
exponential distribution's quantiles at evenly spaced probabilities, in
an order drawn from that seed.  Every run of a cell then offers the same
arrivals, and only the prompts' ids change with ``--seed``: a tail over
a hundred requests moves by several percent with the order of the gaps,
which would hide what a change to the system does to it.
"""

from __future__ import annotations

import math

import numpy as np

# Stream tags keep the draws of one seed independent of each other.
_PROMPTS, _ARRIVALS, _SAMPLE, _WARM = 1, 2, 3, 4


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def prompts(seed: int, index: int, count: int, prompt_len: int,
            vocab: int, stream: int = _PROMPTS) -> np.ndarray:
    """``count`` prompts of ``prompt_len`` ids, uniform over the
    vocabulary; ``index`` numbers the batch (closed loop) or the first
    request (open loop)."""
    return rng(seed, stream, index).integers(
        0, vocab, size=(count, prompt_len), dtype=np.int32)


def warm_up_prompts(seed: int, count: int, prompt_len: int,
                    vocab: int) -> np.ndarray:
    """Prompts for set-up, apart from every prompt of the window."""
    return prompts(seed, count, count, prompt_len, vocab, _WARM)


def arrival_times(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop at
    ``rate_per_s`` over ``seconds``: ``round(rate * seconds)`` requests,
    the first due at 0, their gaps in an order drawn from ``seed``."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s")
    n = max(1, round(rate_per_s * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_per_s
    gaps = rng(seed, _ARRIVALS).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def sample(seed: int, n: int, k: int) -> list:
    """``k`` of ``n`` request indices for the correctness check, drawn
    from the seed, in increasing order."""
    k = min(k, n)
    return sorted(int(i) for i in rng(seed, _SAMPLE).choice(n, k,
                                                            replace=False))


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0 < q < 1) by linear interpolation between
    order statistics, over all values."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    x = q * (len(v) - 1)
    lo = math.floor(x)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)
