"""The one traffic generator: every mix is a data file of parameters.

``full_graph``: a closed loop of whole-graph passes; its parameters are
where the features and tiles live (``residency``) and the program's
device budget (``resident_budget_bytes``), both optional.

``minibatch``: per-user target requests in an open loop, ``rate_rps``
on average.  The pool of requests is drawn from the mix's
``pool_seed``, request ``i`` from its own stream, so every run serves
the same sizes and a shorter run's pool is the start of a longer one's;
the run's ``--seed`` only puts the requests and the gaps between them
in another order.  Each request asks for ``targets_min..targets_max``
distinct vertices (count uniform), each vertex drawn Zipf(``zipf_s``)
over a seeded random permutation of the graph's vertices.  Arrivals
(``arrivals``) are ``"poisson"``: exponential gaps, scaled so the last
arrival falls at the end of the window; or ``"onoff"``: the same
Poisson process run only during bursts of ``on_s`` seconds that
alternate with ``off_s`` seconds of silence, so the mean rate stays
``rate_rps`` and the rate inside a burst is ``(on_s + off_s) / on_s``
times that.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

Request = Tuple[Tuple[int, ...], int]      # (targets, sampling seed)


def pool_size(tr: dict, seconds: float) -> int:
    return max(1, int(round(tr["rate_rps"] * seconds)))


def request_pool(tr: dict, n_vertices: int, n: int) -> List[Request]:
    """The mix's first ``n`` requests, in pool order."""
    perm = np.random.default_rng(tr["pool_seed"]).permutation(n_vertices)
    cdf = np.cumsum(np.arange(1, n_vertices + 1, dtype=np.float64)
                    ** -tr["zipf_s"])
    cdf /= cdf[-1]
    pool = []
    for i in range(n):
        rng = np.random.default_rng([tr["pool_seed"], 1, i])
        t = int(rng.integers(tr["targets_min"], tr["targets_max"] + 1))
        chosen: List[int] = []
        while len(chosen) < t:
            for r in np.searchsorted(cdf, rng.random(2 * t)):
                v = int(perm[min(int(r), n_vertices - 1)])
                if v not in chosen and len(chosen) < t:
                    chosen.append(v)
        pool.append((tuple(chosen), int(rng.integers(2 ** 31))))
    return pool


def arrivals(tr: dict, n: int, seconds: float) -> np.ndarray:
    """Due times of ``n`` requests, in seconds from the window's start,
    in pool order (the caller reorders the gaps)."""
    kind = tr.get("arrivals", "poisson")
    gaps = np.random.default_rng([tr["pool_seed"], 2]).exponential(
        1.0 / tr["rate_rps"], n)
    if kind == "poisson":
        return gaps * (seconds / gaps.sum())
    if kind == "onoff":
        on, off = float(tr["on_s"]), float(tr["off_s"])
        return gaps * (seconds * on / (on + off) / gaps.sum())
    raise ValueError(f"unknown arrivals {kind!r}")


def _wall(tr: dict, t_on: np.ndarray) -> np.ndarray:
    """Wall-clock due times from times on the burst clock."""
    if tr.get("arrivals", "poisson") != "onoff":
        return t_on
    on, off = float(tr["on_s"]), float(tr["off_s"])
    k = np.minimum(np.floor(t_on / on), np.ceil(t_on[-1] / on) - 1)
    return t_on + k * off


def minibatch(tr: dict, n_vertices: int, seed: int, seconds: float
              ) -> Tuple[List[Request], List[int], List[float]]:
    """``(pool, order, due)``: the run's requests are ``pool[order[i]]``
    for ``i = 0, 1, ...``, request ``i`` due ``due[i]`` seconds after
    the window opens."""
    n = pool_size(tr, seconds)
    pool = request_pool(tr, n_vertices, n)
    gaps = arrivals(tr, n, seconds)
    order = np.random.default_rng([seed, 1]).permutation(n)
    gap_order = np.random.default_rng([seed, 2]).permutation(n)
    due = _wall(tr, np.cumsum(gaps[gap_order]))
    return pool, order.tolist(), due.tolist()
