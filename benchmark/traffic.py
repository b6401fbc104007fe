"""The one traffic generator. A mix is a data file under ``traffic/``; this
module turns it, a seed and a window length into what the feeds send:
texts, and for an open loop the time at which each is due.

A schedule is a function of (mix, seed, seconds) alone. Every seed gets the
same multiset of lengths and of gaps between arrivals — the quantiles of
the mix's distributions — in an order of its own, so two seeds give the
system the same work and differ only in how it falls.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()

#: tokens the tokenizer adds to a text's words (CLS and SEP)
SPECIAL_TOKENS = 2


@dataclasses.dataclass
class Stream:
    """One feed's events, in the order they are sent."""

    loop: str  # "open" | "closed"
    texts: list[str]
    tokens: np.ndarray  # [n] tokens per text, specials included
    due_s: np.ndarray | None  # [n] seconds after the window opens (open loop)
    in_flight: int | None  # the closed loop's budget
    autocommit_ms: int | None


@dataclasses.dataclass
class Schedule:
    documents: Stream | None
    queries: Stream | None


def _quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def token_counts(spec: dict, n: int) -> np.ndarray:
    """``n`` token counts: the distribution's quantiles, clipped."""
    p = _quantile_grid(n)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(q) for q in p])
        values = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        values = spec["min"] + p * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    return np.clip(np.floor(values), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times of an open loop: ``round(rate * seconds)`` arrivals whose
    gaps are the exponential distribution's quantiles in the seed's order,
    laid over the window — or over its "on" phases where the mix bursts."""
    n = int(round(spec["rate_per_s"] * seconds))
    if n <= 0:
        return np.zeros(0)
    if spec.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {spec['arrivals']!r}")
    gaps = -np.log1p(-_quantile_grid(n))
    rng.shuffle(gaps)
    burst = spec.get("burst")
    active = seconds
    if burst:
        cycle = burst["on_s"] + burst["off_s"]
        active = seconds * burst["on_s"] / cycle
    at = (np.cumsum(gaps) - gaps) * (active / gaps.sum())
    if burst:
        at = np.floor(at / burst["on_s"]) * cycle + np.mod(at, burst["on_s"])
    return at


def _texts(tokens: np.ndarray, vocab: np.ndarray, rng: np.random.Generator) -> list[str]:
    words = tokens - SPECIAL_TOKENS
    picks = vocab[rng.integers(0, len(vocab), int(words.sum()))]
    ends = np.cumsum(words)
    return [
        " ".join(picks[end - n : end]) for n, end in zip(words.tolist(), ends.tolist())
    ]


def _stream(spec: dict | None, seconds: float, rng, vocab) -> Stream | None:
    if spec is None:
        return None
    if spec["loop"] == "open":
        due = arrival_times(spec, seconds, rng)
        n = len(due)
    elif spec["loop"] == "closed":
        due = None
        n = int(round(spec["pool_per_s"] * seconds))
    else:
        raise ValueError(f"unknown loop {spec['loop']!r}")
    tokens = token_counts(spec["tokens"], n)
    rng.shuffle(tokens)
    return Stream(
        loop=spec["loop"],
        texts=_texts(tokens, vocab, rng),
        tokens=tokens,
        due_s=due,
        in_flight=spec.get("in_flight"),
        autocommit_ms=spec.get("autocommit_ms"),
    )


def build(mix: dict, seed: int, seconds: float) -> Schedule:
    vocab = np.array([f"w{i}" for i in range(mix["vocabulary_words"])], dtype=object)
    # documents and queries draw from generators of their own, so a mix
    # that adds queries sends the same documents as one without
    doc_rng = np.random.default_rng([seed, 1])
    query_rng = np.random.default_rng([seed, 2])
    return Schedule(
        documents=_stream(mix.get("documents"), seconds, doc_rng, vocab),
        queries=_stream(mix.get("queries"), seconds, query_rng, vocab),
    )


def seq_buckets(spec: dict, minimum: int) -> list[int]:
    """The power-of-two sequence lengths, from ``minimum`` up, that texts of
    this stream can pad to."""
    out, b = [], minimum
    while b < spec["tokens"]["min"]:
        b *= 2
    while True:
        out.append(b)
        if b >= spec["tokens"]["max"]:
            return out
        b *= 2
