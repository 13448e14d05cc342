"""The one traffic generator: a mix's parameters and a seed -> requests.

Every seed serves the same schedule: the same requests (prompt and output
lengths, stratified quantiles of the mix's distributions, paired and
ordered once) due at the same times (stratified exponential gaps, ordered
once). The seed draws the prompt tokens (and, in the harness, the weights).
With some twenty requests in a window, shuffling the order by seed moved
the TTFT tail by 30% between seeds against 3% between two runs of one seed
on one TPU v5e, so the order is fixed and the spread between runs is the
system's, not the generator's.

Mix parameters (``bench/traffic/<mix>.json``):

    arrivals        {"kind": "poisson", "rate_per_s": r}: n = round(r * T)
                    requests over a window of T seconds, gaps exponential
                    {"kind": "backlog", "requests": n}: all due at 0
    prompt_tokens   {"median", "sigma", "min", "max"}: lognormal, clipped
    output_tokens   the same, for the tokens each request asks for
    failures        [{"at": fraction of the window, "kind": "aw"|"ew",
                      "worker": id, or "protected": the EW the shadow
                      experts cover when the failure strikes}]
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    rid: str
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


@dataclass
class Failure:
    at: float           # seconds after the window opens
    kind: str           # "aw" | "ew"
    worker: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(p: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(x, p["min"], p["max"]).astype(np.int64)


def arrival_times(a: dict, seconds: float) -> np.ndarray:
    if a["kind"] == "backlog":
        return np.zeros(a["requests"])
    if a["kind"] == "poisson":
        rate = a["rate_per_s"]
        n = max(1, round(rate * seconds))
        gaps = -np.log1p(-_quantiles(n)) / rate
        return np.cumsum(np.random.default_rng(1).permutation(gaps))
    raise ValueError(f"unknown arrival kind {a['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests of one run, in due order: the mix's schedule, which is
    the same for every seed, with prompt tokens drawn from the seed."""
    due = arrival_times(mix["arrivals"], seconds)
    n = len(due)
    # fixed (prompt, output) pairs in a fixed order: seed 0 of the
    # generator shuffles the stratified lengths once, for every run
    order = np.random.default_rng(0)
    prompts = order.permutation(lognormal_lengths(mix["prompt_tokens"], n))
    outputs = order.permutation(lognormal_lengths(mix["output_tokens"], n))
    reqs = []
    for i in range(n):
        toks = np.random.default_rng([seed, 1, i]).integers(
            0, vocab, int(prompts[i]), dtype=np.int32)
        reqs.append(Request(f"r{i:05d}", float(due[i]), toks,
                            int(outputs[i])))
    return reqs


def failures(mix: dict, seconds: float) -> List[Failure]:
    return [Failure(f["at"] * seconds, f["kind"], f["worker"])
            for f in mix.get("failures", [])]
