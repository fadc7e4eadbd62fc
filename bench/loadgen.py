"""One seeded traffic generator for every mix under ``bench/traffic/``.

A mix file (JSON) holds only parameters. The generator turns it and a run
seed into requests; the program never sees the seed.

Every seed gets the same *set* of sizes and gaps: lengths and inter-arrival
gaps are quantiles of the stated distributions at evenly spaced
probabilities, drawn from the mix alone, and the run seed only permutes them
and draws the token ids. So two seeds do the same work in another order, and
the spread between runs is the system's, not the sampler's.

Kinds:

* ``open_poisson``: independent users. ``round(rate_rps * (warm_s +
  seconds))`` requests whose gaps are exponential quantiles (mean ``1 / rate_rps``), each
  with a prompt and an output length. Times are due times from the window's
  start; arrivals begin ``warm_s`` before it, so the window opens on a
  steady queue. The driver submits each when due, whatever the server is
  doing.
* ``closed_sessions``: ``sessions`` callers that each wait for their reply.
  The first ``sessions`` start at set-up, and each one that ends is
  replaced by the next from a pool of further sessions.
* ``train_stream``: training batches of ``batch`` rows of ``seq_len``
  tokens, a copy of the program's synthetic token stream (Zipf unigrams
  with a k-gram overlay) keyed by (seed, step).
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n values of a length distribution at probabilities (i + 0.5) / n."""
    p = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        v = spec["min"] + p * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def max_len(spec: dict) -> int:
    return int(spec["max"])


@dataclasses.dataclass
class Job:
    """One request: due time (s from the window's start; None in a closed
    loop), prompt token ids, and how many tokens to generate."""
    due: float | None
    prompt: np.ndarray
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def open_poisson(mix: dict, seed: int, seconds: float, vocab: int) -> list[Job]:
    """Requests due from ``-warm_s`` (arrivals before the window opens)
    to the window's end."""
    warm = float(mix.get("warm_s", 0))
    n = max(1, int(round(mix["rate_rps"] * (warm + seconds))))
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p) / mix["rate_rps"]
    prompts = _quantiles(mix["prompt_tokens"], n)
    outputs = _quantiles(mix["output_tokens"], n)
    rng = _rng(seed, 0)
    gaps, prompts, outputs = (rng.permutation(a) for a in
                              (gaps, prompts, outputs))
    # the first request is due when arrivals start: every run starts with
    # the same load, whatever gap the permutation put first
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) - warm
    return [Job(float(t), rng.integers(0, vocab, int(pl), dtype=np.int64),
                int(ol)) for t, pl, ol in zip(due, prompts, outputs)]


def closed_sessions(mix: dict, seed: int, vocab: int) -> list[Job]:
    """The whole session pool, in start order: the first ``sessions`` open
    at set-up, the rest replace sessions as they end. The pool is waves of
    ``sessions`` sessions, each wave the same stratified set of context and
    output lengths in its own order, so every seed opens the same set."""
    n = int(mix["sessions"])
    waves = -(-int(mix["pool"]) // n)
    ctx = _quantiles(mix["context_tokens"], n)
    out = _quantiles(mix["output_tokens"], n)
    rng = _rng(seed, 1)
    jobs = []
    for _ in range(waves):
        for c, o in zip(rng.permutation(ctx), rng.permutation(out)):
            jobs.append(Job(None, rng.integers(0, vocab, int(c),
                                               dtype=np.int64), int(o)))
    return jobs


def train_batch(mix: dict, seed: int, step: int, vocab: int) -> dict:
    """Rows of (seed, step): Zipf-ish unigrams, every third row (by row +
    position) overlaid with a deterministic k-gram so a learning signal
    exists. Same arithmetic as the program's synthetic token stream."""
    b, s, k = int(mix["batch"]), int(mix["seq_len"]), int(mix["structure"])
    rng = np.random.default_rng((int(seed), int(step)))
    ranks = rng.integers(1, 1000, size=(b, s + 1))
    base = (vocab * (ranks.astype(np.float64) ** -1.1)).astype(np.int64) % vocab
    rows = np.arange(b)
    for t in range(k, s + 1):
        mask = (rows + t) % 3 == 0
        base[mask, t] = (base[mask, t - k] * 31 + 7) % vocab
    return {"inputs": base[:, :-1].astype(np.int32),
            "labels": base[:, 1:].astype(np.int32)}


def engine_sizes(mix: dict) -> dict:
    """Engine settings of a serving mix: the mix's own, plus ``max_seq_len``
    sized as the program's launcher sizes it (longest prompt + longest
    output) unless the mix states it."""
    eng = dict(mix["engine"])
    if "max_seq_len" not in eng:
        key = "prompt_tokens" if "prompt_tokens" in mix else "context_tokens"
        eng["max_seq_len"] = max_len(mix[key]) + max_len(mix["output_tokens"])
    return eng

