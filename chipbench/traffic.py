"""The one traffic generator: a job of requests from a mix file and a seed.

A mix (``traffic/<name>.json``) gives the batch the server runs at, the
cache capacity, the number of requests in a job and two length profiles
(prompt tokens and output tokens).  A profile is the ``n`` quantiles
``(k + 0.5) / n`` of a log-normal with the given median and ``sigma``,
clipped to ``[min, max]``, rounded to a multiple of ``align`` (prompts)
and put in one fixed order.  So every seed gets the same lengths and
budgets in the same order, and with them the same prefill and decode
shapes; the seed sets only the tokens of the prompts (and, in ``run.py``,
the weights).  Every job is offline: all its requests arrive at once.

Prompts are printable ASCII, one byte token each after the BOS token,
as in ``chip_smoke.seeded_prompts``.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

ORDER_SEED = 0      # the fixed order of lengths, the same for every seed


def profile(p: dict, n: int, align: int = 1) -> list:
    """``n`` lengths of profile ``p`` (keys ``min``, ``max``, ``median``,
    ``sigma``; ``median`` and ``sigma`` may be left out when
    ``min == max``), in the profile's fixed order."""
    lo, hi = int(p["min"]), int(p["max"])
    if lo > hi:
        raise ValueError(f"profile min {lo} > max {hi}")
    if lo == hi:
        out = [lo] * n
    else:
        mu, sigma = math.log(float(p["median"])), float(p["sigma"])
        z = [NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)]
        out = [min(hi, max(lo, round(math.exp(mu + sigma * zk))))
               for zk in z]
    out = [-(-x // align) * align for x in out]
    order = np.random.default_rng(ORDER_SEED).permutation(n)
    return [int(out[i]) for i in order]


def job(mix: dict, seed: int):
    """``(prompts, budgets)`` of one job: prompt ``k`` has exactly
    ``prompt_lens[k]`` tokens counting BOS, and may emit ``budgets[k]``
    tokens."""
    n = int(mix["requests"])
    lens = profile(mix["prompt_tokens"], n, int(mix.get("prompt_align", 1)))
    budgets = profile(mix["output_tokens"], n)
    rng = np.random.default_rng(seed)
    prompts = ["".join(map(chr, rng.integers(32, 127, L - 1)))
               for L in lens]
    return prompts, budgets
