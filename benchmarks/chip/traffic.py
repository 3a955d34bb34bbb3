"""One generator for every traffic mix in ``traffic/<mix>.json``.

A mix file gives the arrival kind, the length ranges (inclusive, in
tokens) and the timing of a run:

- ``"arrivals": "stratified_poisson"`` with ``rate_per_s`` and
  ``stratum_s``: an open loop whose gaps follow the exponential
  distribution, stratified. Each span of a run (ramp, window, drain) of
  ``L`` seconds holds ``round(rate * L)`` requests, dealt evenly into
  strata of about ``stratum_s`` seconds. A stratum's gaps are the
  exponential distribution's quantiles scaled to fill it, its sizes the
  length ranges' quantiles, and ``--seed`` only orders them. So every
  seed offers each stratum the same requests at the same mean rate, in
  another order: the bursts an order of exponential gaps makes inside a
  stratum, but neither the swing in a span's count that a Poisson count
  has nor long requests bunched across strata, either of which would let
  the seed change the work measured.
- ``"arrivals": "backlog"`` with ``backlog_requests``: an offline batch,
  every request due at time 0, taken in order of id. Sizes come in blocks
  of ``block_requests``, each block the length ranges' quantiles in the
  seed's order, so the window's run of ids holds nearly the same sizes
  whatever the seed.

``output_tokens`` counts the tokens delivered to the host, the first one
(the prefill's) included. Prompt token ids come from the seed and the
request id alone; the program receives them through its content store.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass(frozen=True)
class Arrival:
    rid: int
    due: float          # seconds after the first request is due
    plen: int           # requested prompt tokens
    out: int            # tokens to deliver, the prefill's first included


def _quantile_ints(lo: int, hi: int, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(np.int64)


def _sizes(mix: dict, rng, n: int):
    return (rng.permutation(_quantile_ints(*mix["prompt_tokens"], n)),
            rng.permutation(_quantile_ints(*mix["output_tokens"], n)))


def schedule(mix: dict, seed: int, spans: Sequence[float]) -> List[Arrival]:
    """Every request of the run, in due order; rids count from 1. An open
    loop fills each of ``spans`` (seconds: ramp, window, drain) on its own;
    a backlog is all of its ``backlog_requests``, due at 0."""
    kind = mix["arrivals"]
    out: List[Arrival] = []
    if kind == "backlog":
        n, b = int(mix["backlog_requests"]), int(mix["block_requests"])
        for k in range(-(-n // b)):
            rng = np.random.default_rng(np.random.SeedSequence([seed, 7, k]))
            for p, o in zip(*_sizes(mix, rng, b)):
                if len(out) < n:
                    out.append(Arrival(len(out) + 1, 0.0, int(p), int(o)))
        return out
    if kind != "stratified_poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    rate, t0 = float(mix["rate_per_s"]), 0.0
    for k, length in enumerate(spans):
        n = int(round(rate * length))
        m = max(1, int(round(length / float(mix["stratum_s"]))))
        for j in range(m):
            nj = int(round(n * (j + 1) / m)) - int(round(n * j / m))
            if not nj:
                continue
            rng = np.random.default_rng(
                np.random.SeedSequence([seed, 7, k, j]))
            gaps = -np.log1p(-(np.arange(nj) + 0.5) / nj)
            gaps = rng.permutation(gaps * (length / m / gaps.sum()))
            due = t0 + length * j / m + np.concatenate(
                [[0.0], np.cumsum(gaps)[:-1]])
            for t, p, o in zip(due, *_sizes(mix, rng, nj)):
                out.append(Arrival(len(out) + 1, float(t), int(p), int(o)))
        t0 += length
    return out


def prompt_tokens(seed: int, rid: int, plen: int, vocab: int) -> np.ndarray:
    """The requested prompt of request ``rid``: ``plen`` ids in
    [0, vocab)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11, rid]))
    return rng.integers(0, vocab, plen, dtype=np.int64).astype(np.int32)


def padded(tokens: np.ndarray, bucket: int) -> np.ndarray:
    """The prompt as the runtime prefills it: right-padded with id 0 to its
    length bucket. The pad joins the context, so the reference is given
    the same ids."""
    out = np.zeros(bucket, np.int32)
    out[:tokens.shape[0]] = tokens
    return out
