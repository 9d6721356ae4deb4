"""The one traffic generator: reads a mix file (``bench/traffic/<name>.json``)
and makes a run's requests from its seed.

Every seed gets the same multiset of prompt lengths, output lengths,
inter-arrival gaps and greedy flags, taken at evenly spaced quantiles of
the mix's distributions; the seed only orders them and draws the token ids.
The order is balanced (``balanced``): each run of about ``BLOCK``
consecutive requests holds one prompt length, output length, gap and
greedy flag from each of about ``BLOCK`` equal slices of their sorted
values, so that no seed piles its longest prompts or its shortest gaps
into one stretch of the window. So two seeds offer the same work in another order,
spread alike over the window, and the spread between runs is the system's
and not the generator's.

A mix file holds:

* ``arrivals``: the gaps between due times, in seconds (an open loop:
  each request is due on its schedule, whatever the server does);
* ``prompt`` and ``output``: lengths in tokens;
* ``greedy_share`` and ``sampled`` (``temperature``, ``top_k``) for the
  rest.

Each of ``arrivals``, ``prompt`` and ``output`` names its distribution,
``{"dist": "<name>", ...parameters}``, and ``dists/<name>.py`` gives its
values at quantiles (``at(spec, u)``). A new distribution is a new file
there.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: requests per balanced block (``balanced``)
BLOCK = 8


@dataclass
class RequestSpec:
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int
    temperature: float
    top_k: int
    due_s: float                 # seconds after the window's start

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def quantiles(n: int) -> np.ndarray:
    """n evenly spaced probabilities, (i + 1/2) / n."""
    return (np.arange(n) + 0.5) / n


def values(spec: dict, n: int) -> np.ndarray:
    """n values of ``spec``'s distribution at evenly spaced quantiles,
    ascending."""
    name = spec["dist"]
    path = os.path.join(HERE, "dists", name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"unknown distribution {name!r} (no {path})")
    mod = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location("bench_dist_" + name, path))
    mod.__spec__.loader.exec_module(mod)
    return np.asarray(mod.at(spec, quantiles(n)))


def lengths(spec: dict, n: int) -> np.ndarray:
    return values(spec, n).astype(np.int64)


def arrivals(mix: dict, rate: Optional[float] = None) -> dict:
    """The mix's arrival spec, at ``rate`` requests a second if given."""
    spec = dict(mix["arrivals"])
    if rate is not None:
        spec["rate_per_s"] = rate
    return spec


def count(mix: dict, seconds: float, rate: Optional[float] = None) -> int:
    return max(int(math.ceil(arrivals(mix, rate)["rate_per_s"] * seconds)),
               1)


def balanced(rng: np.random.Generator, ascending: np.ndarray,
             block: int) -> np.ndarray:
    """``ascending`` (n values, sorted) in an order drawn from ``rng``.
    The places are cut into ``m = ceil(n / block)`` runs of consecutive
    places, the ranks into slices of ``m`` consecutive ranks; each run of
    places holds one value of each full slice and at most one of the last,
    in a random order."""
    n = len(ascending)
    blocks = max(-(-n // block), 1)
    where = np.empty(n, np.int64)
    for lo in range(0, n, blocks):
        hi = min(lo + blocks, n)
        where[lo:hi] = rng.permutation(blocks)[:hi - lo]
    order = np.concatenate([rng.permutation(np.flatnonzero(where == b))
                            for b in range(blocks)])
    return ascending[order]


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 20251])


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[RequestSpec]:
    """The requests of one run: as many as ``rate`` (default the mix's)
    offers in ``seconds``, in order of their due times, the first due at 0
    and the gaps' sum spread evenly over the window."""
    rng = _rng(seed)
    n = count(mix, seconds, rate)
    prompts = balanced(rng, lengths(mix["prompt"], n), BLOCK)
    outputs = balanced(rng, lengths(mix["output"], n), BLOCK)
    greedy = np.zeros(n, bool)
    greedy[n - int(round(float(mix.get("greedy_share", 1.0)) * n)):] = True
    greedy = balanced(rng, greedy, BLOCK)
    sampled = mix.get("sampled", {})
    gaps = balanced(rng, values(arrivals(mix, rate), n), BLOCK)
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
        * (seconds / gaps.sum())
    return [RequestSpec(
        prompt=rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
        max_new_tokens=int(outputs[i]),
        temperature=0.0 if greedy[i] else float(sampled["temperature"]),
        top_k=0 if greedy[i] else int(sampled["top_k"]),
        due_s=float(dues[i])) for i in range(n)]
