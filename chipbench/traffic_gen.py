"""The benchmark's one traffic generator: open-loop arrivals and request
lengths, read from a mix file (``traffic/<mix>.json``).

Seed-invariant work.  A run's numbers should differ between seeds by
timing noise, not by a different amount of work, so every seed gets the
*same* multiset of inter-arrival gaps, prompt lengths and output lengths
in another order: the values are the distribution's quantiles at
``(i + 0.5) / n`` (stratified sampling), and the seed only orders them
and draws the token ids.  This is not an i.i.d. draw, and the mix file
says so by the names of its kinds:

* ``arrivals: "poisson_stratified"``: ``n = round(rate * span_s)``
  exponential gaps at the stratified quantiles, in plain seeded order
  (so arrivals keep their bursts), scaled so that the ``n`` arrivals
  span exactly ``span_s``.
* ``lengths: "lognormal_stratified_balanced"``: ``n`` lognormal lengths
  at the stratified quantiles (no tail beyond the ``1/(2n)`` quantile),
  in balanced order: split into ``BLOCK`` strata from short to long,
  every run of ``BLOCK`` consecutive requests takes one length from each
  stratum, so no stretch of the window is all long or all short
  requests.

Other mix keys:

* ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max",
  "round_up"}`` of the lognormal; values are clipped to ``[min, max]``,
  then rounded up to a multiple of ``round_up``.
* ``token_ids``: ``"uniform"`` over the configuration's vocabulary.

The arrival and lognormal-length idea follows
``src/repro/serving/workload.py``; this is the benchmark's own copy, so
that a change to the program cannot change the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_STD_NORMAL = NormalDist()
BLOCK = 8


@dataclasses.dataclass
class Arrival:
    rid: int
    due_s: float            # seconds from the schedule's origin
    prompt: np.ndarray      # (prompt_len,) int32
    out_len: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def balanced_order(n: int, rng: np.random.Generator,
                   block: int = BLOCK) -> np.ndarray:
    """A seeded order of ``range(n)`` (indices of sorted values) in which
    every ``block`` consecutive positions take one index from each of
    ``block`` contiguous strata."""
    strata = [rng.permutation(s) for s in np.array_split(np.arange(n),
                                                          block)]
    out = []
    for i in range(max(len(s) for s in strata)):
        row = [s[i] for s in strata if i < len(s)]
        out.extend(rng.permutation(row))
    return np.asarray(out, np.int64)


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator
                      ) -> np.ndarray:
    """``n`` stratified lognormal lengths, clipped, rounded up, in
    balanced order (``lengths: "lognormal_stratified_balanced"``)."""
    z = np.array([_STD_NORMAL.inv_cdf(u) for u in _quantiles(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    vals = np.clip(np.ceil(vals), spec["min"], spec["max"])
    step = int(spec.get("round_up", 1))
    vals = np.ceil(vals / step) * step
    return vals.astype(np.int64)[balanced_order(n, rng)]


def arrival_times(kind: str, n: int, span_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times in ``[0, span_s)``: stratified exponential
    gaps in seeded order, scaled so ``n`` arrivals span ``span_s``
    (``arrivals: "poisson_stratified"``)."""
    if kind != "poisson_stratified":
        raise ValueError(f"unknown arrival process {kind!r}")
    gaps = -np.log1p(-_quantiles(n + 1))
    gaps = rng.permutation(gaps)
    cum = np.cumsum(gaps)
    return span_s * cum[:n] / cum[n]


def schedule(mix: dict, rate: float, span_s: float, vocab: int,
             seed: int) -> List[Arrival]:
    """The open-loop schedule of ``round(rate * span_s)`` requests."""
    n = max(1, int(round(rate * span_s)))
    seed = int(seed)
    times = arrival_times(mix["arrivals"], n, span_s,
                          np.random.default_rng([seed, 1]))
    if mix["lengths"] != "lognormal_stratified_balanced":
        raise ValueError(f"unknown length draw {mix['lengths']!r}")
    prompts = lognormal_lengths(mix["prompt_len"], n,
                                np.random.default_rng([seed, 2]))
    outs = lognormal_lengths(mix["output_len"], n,
                             np.random.default_rng([seed, 3]))
    if mix.get("token_ids", "uniform") != "uniform":
        raise ValueError(f"unknown token_ids {mix['token_ids']!r}")
    tok_rng = np.random.default_rng([seed, 4])
    return [Arrival(rid=i, due_s=float(times[i]),
                    prompt=tok_rng.integers(0, vocab, size=int(prompts[i]),
                                            dtype=np.int64).astype(np.int32),
                    out_len=int(outs[i]))
            for i in range(n)]


def prompt_buckets(mix: dict) -> List[int]:
    """Every prompt length the mix can draw (one prefill shape each)."""
    spec = mix["prompt_len"]
    step = int(spec.get("round_up", 1))
    lo = int(math.ceil(spec["min"] / step) * step)
    hi = int(math.ceil(spec["max"] / step) * step)
    return list(range(lo, hi + 1, step))
