"""The one generator of traffic.  A mix is a data file of
``bench/traffic`` (lengths and the kind of loop); a cell's file in
``bench/cells`` adds its rate or client count.

Every seed gets the same work in another order.  The lengths come in
rounds: a closed loop's round is one request for each client, and each
round holds the quantiles of the prompt and of the output distribution at
as many points as it has requests, so every round, and every window that
holds whole rounds, has the same lengths.  The seed draws which client
gets which prompt length and which output length in each round (and the
order of an open loop's lengths and gaps between arrivals), and the token
ids.  (When the seed drew the lengths themselves, runs of one closed-loop
cell on different seeds spread 10-40% while runs on one seed agreed
within 0.1-3%.)
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

from bench.model import BENCH_DIR


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None   # open loop: seconds from window open
    client: Optional[int] = None  # closed loop: the client that sends it


def load_mix(name: str, root: Path = BENCH_DIR) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 1/2) / n of ``dist``."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + p * (dist["max"] + 1 - dist["min"]) - 0.5
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.round(v), dist["min"], dist["max"]).astype(np.int64)


def longest(mix: dict) -> int:
    """The longest sequence a request of ``mix`` can reach."""
    return mix["prompt"]["max"] + mix["output"]["max"]


def _items(mix, seed, rounds, size, vocab, first_rid):
    """``rounds`` rounds of ``size`` requests, each round the ``size``
    quantiles of both length distributions, in the seed's order."""
    order = np.random.default_rng([seed, 1])
    p, o = quantiles(mix["prompt"], size), quantiles(mix["output"], size)
    prompts = np.concatenate([order.permutation(p) for _ in range(rounds)])
    outs = np.concatenate([order.permutation(o) for _ in range(rounds)])
    toks = np.random.default_rng(seed).integers(0, vocab, int(prompts.sum()),
                                                dtype=np.int64)
    cuts = np.concatenate([[0], np.cumsum(prompts)])
    return [Item(rid=first_rid + i,
                 prompt=toks[cuts[i]:cuts[i + 1]].astype(np.int32),
                 max_new=int(outs[i]))
            for i in range(rounds * size)]


def open_loop(mix: dict, seed: int, rate: float, start: float, end: float,
              vocab: int, first_rid: int = 0) -> List[Item]:
    """Poisson arrivals at ``rate`` per second from ``start`` to about
    ``end`` seconds (relative to window open)."""
    n = max(1, math.ceil(rate * (end - start)))
    items = _items(mix, seed, 1, n, vocab, first_rid)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = start + np.cumsum(np.random.default_rng([seed, 2]).permutation(
        gaps))
    for it, t in zip(items, due):
        it.due = float(t)
    return items


def closed_loop(mix: dict, seed: int, clients: int, per_client: int,
                vocab: int, first_rid: int = 0) -> List[List[Item]]:
    """``per_client`` requests for each of ``clients`` clients, each sent
    when the client's previous one has finished; a client's n-th request
    is in round n."""
    items = _items(mix, seed, per_client, clients, vocab, first_rid)
    queues = [items[c::clients] for c in range(clients)]
    for c, q in enumerate(queues):
        for it in q:
            it.client = c
    return queues
