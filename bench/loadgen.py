"""The one traffic generator: open-loop requests behind a shared prefix.

A traffic mix is a JSON file of parameters under ``traffic/``; this module
reads any of them. Requests arrive on a wall-clock schedule, a Poisson
process at the cell's rate. Each prompt is the mix's shared prefix (the
part a prefix cache can serve) followed by tokens of its own, and each asks
for a number of greedy output tokens; prompt and output together fit the
context.

Every gap, length and output budget is the middle of one of ``n`` equal
quantile strata of its distribution, and which stratum the ``i``-th request
of a span takes is fixed: a permutation drawn from a constant key, the same
for every seed. A mix, a rate and a span therefore give the same schedule
of sizes and due times for every seed; the seed picks the token ids (and,
elsewhere, the weights). The distributions keep their tails, and the tails
of what is measured over them no longer move with the seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# constant keys of the fixed stratum orders, one per drawn quantity
_GAPS, _PROMPT, _OUTPUT = 1, 2, 3


def strata(n: int, key: int) -> np.ndarray:
    """The middle of each of ``n`` equal strata of (0, 1), in an order fixed
    by ``key`` and ``n`` alone."""
    order = np.random.default_rng([0x10AD, key, n]).permutation(n)
    return (order + 0.5) / n


def quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    """The ``u`` quantiles of a length distribution ``spec``:
    ``{"dist": "exponential", "mean"}``, the distribution that a published
    mean alone states."""
    if spec["dist"] != "exponential":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return -spec["mean"] * np.log1p(-u)


@dataclass
class Mix:
    """A traffic mix file's parameters."""
    prefix_tokens: int        # shared by every prompt
    prompt: dict              # each prompt's own tokens after the prefix
    output: dict              # output tokens asked for
    context: int              # prefix + own tokens + output fit in it
    description: str = ""
    source: str = ""
    derivation: dict = None   # the published statistics and the cut

    @classmethod
    def from_json(cls, d: dict) -> "Mix":
        return cls(**d)


@dataclass
class Request:
    due: float                # seconds from the schedule's start
    prompt: np.ndarray
    out_len: int


def schedule(mix: Mix, rng, vocab: int, spans: list) -> list:
    """Every request of a run, in due order. ``spans`` is
    ``[(start_s, span_s, rate), ...]`` (the steady-state warm-up, the
    window, the drain); each span gets exactly ``round(rate * span)``
    requests with a stratified schedule of its own."""
    prefix = rng.integers(0, vocab, mix.prefix_tokens, dtype=np.int32)
    out = []
    for t0, span, rate in spans:
        n = int(round(rate * span))
        if n == 0:
            continue
        gaps = -np.log1p(-strata(n, _GAPS))
        t = np.cumsum(gaps)
        due = t0 + (t - gaps[0] / 2) * (span / t[-1])
        outs = np.maximum(1, np.ceil(quantile(mix.output,
                                              strata(n, _OUTPUT))))
        own = np.maximum(1, np.ceil(quantile(mix.prompt,
                                             strata(n, _PROMPT))))
        for d, o, p in zip(due, outs.astype(int), own.astype(int)):
            o = min(o, mix.context - mix.prefix_tokens - 1)
            p = min(p, mix.context - mix.prefix_tokens - o)
            prompt = np.concatenate(
                [prefix, rng.integers(0, vocab, p, dtype=np.int32)])
            out.append(Request(due=float(d), prompt=prompt, out_len=int(o)))
    return out

