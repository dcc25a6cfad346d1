"""Open-loop traffic: a mix file (``bench/traffic/<mix>.json``) read by one
general schedule and the query generator the file names.

A mix file holds the generator's name (``bench/traffic/<generator>.py``),
its parameters, the lists of query sizes, the fixed offered ``rate`` in
requests per second, and the ``seed`` its requests and their schedule are
drawn from.  A run of ``seconds`` offers ``round(rate * seconds)``
requests.  Every run seed gets the same requests at the same due times; the
run's seed orders the rows inside each request, which changes the inputs
and not the work.  (A request's cost spans two orders of magnitude with its
content, and the server queues: seeds that drew other content moved the
median latency fivefold, and seeds that only reordered the same requests
moved it by a fifth, against a few percent between two runs of one seed.)
The gaps are the stratified quantiles of an exponential distribution at
the mix's rate, shuffled, which makes the arrivals Poisson in
distribution.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench.lake import Lake, rng as seeded


@dataclasses.dataclass
class Request:
    due: float  # seconds after the window opens
    key: np.ndarray  # int32[n_rows, n_cols] lake vocabulary ids, key columns first
    key_width: int  # the first key_width columns are the join key

    @property
    def n_rows(self) -> int:
        return int(self.key.shape[0])


def arrivals(rate: float, n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` due times in (0, seconds]: stratified exponential gaps at
    ``rate``, shuffled by ``rng`` and scaled to end at ``seconds``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    due = np.cumsum(gaps[rng.permutation(n)])
    return due * (seconds / due[-1])


def sizes(mix: dict, n: int) -> list[dict]:
    """The fixed multiset of per-request sizes: every combination of the
    mix's size lists in turn, the first list varying fastest."""
    axes = [(name, mix[name]) for name in mix["vary"]]
    out = []
    for i in range(n):
        pick, stride = {}, 1
        for name, values in axes:
            pick[name] = values[(i // stride) % len(values)]
            stride *= len(values)
        out.append(pick)
    return out


def generate(mix: dict, query, lake: Lake, seed: int, seconds: float) -> list[Request]:
    """The run's requests in due order; ``query`` is the mix generator's
    ``query(lake, mix, size, rng)``.  The requests, their order and their
    gaps are drawn from the mix's own ``seed``; the run's ``seed`` orders
    the rows of each request."""
    n = request_count(mix["rate"], seconds)
    content = seeded(mix["seed"], 3)
    fixed = [query(lake, mix, size, content) for size in sizes(mix, n)]
    schedule = seeded(mix["seed"], 4)
    due = arrivals(mix["rate"], n, seconds, schedule)
    rows = seeded(seed, 3)
    out = []
    for d, i in zip(due, schedule.permutation(n)):
        key, width = fixed[i]
        out.append(Request(due=float(d), key=key[rows.permutation(key.shape[0])], key_width=width))
    return out


def request_count(rate: float, seconds: float) -> int:
    return max(1, math.floor(rate * seconds + 0.5))
