"""One general traffic generator, driven by the files under bench/traffic/.

Every draw is stratified: for the N requests of a set, prompt lengths,
output lengths and inter-arrival gaps are the quantiles (i + 0.5) / N of the
distributions the file states. The seed only permutes their order and draws
the token ids, so every seed serves the same multiset of work.

An open-loop mix is two such sets: a ramp before the window, which brings
the batch to steady occupancy and is never sampled, and the window's own
requests, whose arrival times all fall inside the window. A closed-loop mix
gives each of B clients a fixed sequence of requests, which it sends back to
back: the sequences are the same for every seed, which only orders the
clients and draws the token ids, so the same requests are admitted in every
run's window.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Request:
    """One request of a run, as the generator made it."""
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    due: Optional[float]        # seconds after the ramp starts (open loop)
    phase: str                  # "ramp" or "window"
    client: Optional[int] = None    # the client that sends it (closed loop)


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """Stratified lengths: the (i + 0.5) / n quantiles of ``spec``'s
    distribution, clipped to [min, max] and rounded to its ``grid``."""
    u = quantiles(n)
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    grid = spec.get("grid", 1)
    x = np.round(np.clip(x, lo, hi) / grid) * grid
    return np.clip(x, lo, hi).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """Stratified Poisson inter-arrival gaps: exponential quantiles."""
    return -np.log1p(-quantiles(n)) / rate


def _set(mix: dict, n: int, rng: np.random.Generator, vocab: int,
         phase: str, start: float, first: int) -> List[Request]:
    """n open-loop requests whose arrivals start at ``start``."""
    plens = rng.permutation(lengths(mix["prompt"], n))
    olens = rng.permutation(lengths(mix["output"], n))
    g = rng.permutation(gaps(mix["rate_per_s"], n))
    # the first request of the set arrives at its start; the gaps of the
    # n-1 later ones are n-1 of the n stratified gaps
    due = list(start + np.concatenate([[0.0], np.cumsum(g[:-1])]))
    return [Request(first + i,
                    rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                    int(olens[i]), due[i], phase)
            for i in range(n)]


LAYOUT_SEED = 0     # deals the closed loop's lengths to its clients


def _clients(mix: dict, clients: int, rng: np.random.Generator,
             vocab: int) -> List[Request]:
    """Each client's fixed sequence of requests_per_client requests: the
    stratified lengths dealt to the clients by a fixed key, not the seed.
    The seed orders the clients within each round and draws the ids."""
    per = mix["requests_per_client"]
    n = clients * per
    deal = np.random.default_rng(LAYOUT_SEED)
    plens = deal.permutation(lengths(mix["prompt"], n)).reshape(clients, per)
    olens = deal.permutation(lengths(mix["output"], n)).reshape(clients, per)
    order = rng.permutation(clients)
    reqs: List[Request] = []
    for k in range(per):
        for c in order:
            reqs.append(Request(
                len(reqs),
                rng.integers(0, vocab, int(plens[c, k])).astype(np.int32),
                int(olens[c, k]), None, "window", int(c)))
    return reqs


def window_count(mix: dict, seconds: float) -> int:
    """Requests due in an open-loop window of ``seconds``."""
    return max(1, int(math.floor(mix["rate_per_s"] * seconds)))


def generate(mix: dict, seed: int, vocab: int, seconds: float,
             clients: int) -> List[Request]:
    """All requests of one run, ramp first, in their order of sending."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n_ramp = max(1, int(round(mix["rate_per_s"] * mix["ramp_s"])))
        ramp = _set(mix, n_ramp, rng, vocab, "ramp", 0.0, 0)
        win = _set(mix, window_count(mix, seconds), rng, vocab, "window",
                   mix["ramp_s"], n_ramp)
        return ramp + win
    if mix["loop"] == "closed":
        return _clients(mix, clients, rng, vocab)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def warm_lengths(reqs: List[Request]) -> List[int]:
    """Every distinct prompt length a run will admit, each warmed once."""
    return sorted({len(r.prompt) for r in reqs})
