"""The one general traffic generator: it reads a mix's parameter file and
makes requests and arrival times, or training batches, from ``--seed``.

Lengths are the distribution's own quantiles and gaps the exponential's
(stratified, no sampling noise).  Which length meets which, and which
request comes when, is drawn from the mix's ``population_seed``, so a mix
is one fixed schedule; ``--seed`` fills the prompts with fresh random
tokens (and makes the weights).  The order is no matter for the seed
because it changes the work: which requests are live together decides how
many KV rows a decode step walks, and so the step's time (PERF.md
section 6)."""
from __future__ import annotations

import collections
from statistics import NormalDist

import numpy as np


def length_quantiles(spec: dict, n: int, scale: float = 1.0) -> np.ndarray:
    """``n`` lengths at the (i + 0.5) / n quantiles of the distribution in
    ``spec``, clipped to its ``min``..``max``, times ``scale`` (a rehearsal
    shrinks every length with the model's positions), at least 2."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise SystemExit(f"benchmark: unknown length dist {spec['dist']!r}")
    x = np.clip(x, spec["min"], spec["max"]) * scale
    return np.maximum(2, np.rint(x)).astype(int)


def population(mix: dict, n: int, scale: float = 1.0) -> list:
    """``n`` (prompt_len, out_len) pairs: both marginals stratified, paired
    by a shuffle that depends on the mix alone."""
    rng = np.random.default_rng(mix["population_seed"])
    prompts = length_quantiles(mix["prompt_len"], n, scale)
    outs = length_quantiles(mix["output_len"], n, scale)
    rng.shuffle(outs)
    return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def poisson_offsets(rate: float, span_s: float, rng) -> np.ndarray:
    """Arrival offsets of a Poisson process of ``rate`` over ``span_s``:
    round(rate x span) exponential gaps at their stratified quantiles,
    shuffled by ``rng`` (the mix's, not the run's), stretched so that they
    fill the span exactly."""
    n = max(1, int(round(rate * span_s)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    gaps *= span_s / gaps.sum()
    return np.cumsum(gaps) - gaps[0] * 0.5


def warmup_buckets(lengths) -> list:
    """The power-of-two prompt buckets a set of prompt lengths can reach
    (``DecodeServer`` pads a prompt to the next power of two)."""
    return sorted({1 << max(0, int(n) - 1).bit_length() for n in lengths})


Request = collections.namedtuple("Request", "prompt out_len t_due")


def make_prompt(rng, n: int, vocab: int) -> np.ndarray:
    """Fresh random tokens of the published vocabulary: no shared prefix."""
    return rng.integers(0, vocab, (n,)).astype(np.int32)


def token_stream(rng, B: int, T: int, vocab: int) -> np.ndarray:
    """[B, T + 1] tokens of the deterministic stream next = (3 tok + 1)
    mod 13, spread over the vocabulary (chip_smoke.token_stream)."""
    t = rng.integers(0, 13, (B, 1))
    rows = [t]
    for _ in range(T):
        t = (t * 3 + 1) % 13
        rows.append(t)
    return (np.concatenate(rows, 1) * (vocab // 13)).astype(np.int32)
