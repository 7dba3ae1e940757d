"""Input keys and range-partition edges from a traffic mix's parameters.

One general generator reads every mix; a new mix is a new data file.

- ``key_distribution`` ``npb_is``: NAS Parallel Benchmarks IS keys,
  ``floor(max_key / 4 * (u1 + u2 + u3 + u4))``, drawn as the sum of four
  uniform integers in ``[0, max_key)`` divided by four (the same
  bell-shaped, duplicate-heavy distribution, exact in integers).
- ``edges`` ``sampled``: Spark's ``RangePartitioner``: a sample of
  ``sample_points_per_reducer`` points per reducer, three times over,
  split evenly over the map tasks, and a bound at every equal step of
  the sorted sample, skipping duplicates; reducer r takes the keys in
  ``(bound[r-1], bound[r]]``. Spark seeds the sample per RDD; here the
  sample is drawn once, from the key distribution, with the mix's fixed
  ``sample_seed``, so the ranges are as uneven as a sample makes them
  and the same for every ``--seed``. ``quantile``: the exact quantiles
  of the key distribution, every range the same share.

Every seed gives every map task the same number of keys in each reducer
range (the range's share of the distribution, rounded), so block sizes,
and with them the programs the run compiles, are the same for every
seed; the seed draws the keys inside each range and their order. The
keys are drawn on the host, so nothing but the shuffle touches the
device: a pool of random keys, and of them the first in each range up
to its count. The pool is a few per mille larger than the map, so only
its last keys lean away from the ranges that filled first; the order
does not change the work of a sort.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from math import ceil, comb
from typing import List, Tuple

import numpy as np

DRAWS = {"npb_is": 4}
POOL, CHECK = 1, 3  # the streams drawn from one seed


def seed_words(seed: int, stream: int, index: int) -> np.random.SeedSequence:
    """The seed sequence for ``seed`` (any size), a stream and an index
    in it."""
    return np.random.SeedSequence([int(seed), stream, index])


def _below(e: int, max_key: int, draws: int) -> int:
    """Tuples of ``draws`` integers in ``[0, max_key)`` whose key (their
    sum // draws) is below ``e``: the discrete Irwin-Hall count of sums
    at most ``draws * e - 1``."""
    t = draws * e - 1
    if t < 0:
        return 0
    return sum((-1) ** j * comb(draws, j) * comb(t - j * max_key + draws,
                                                 draws)
               for j in range(draws + 1) if t - j * max_key >= 0)


def draw_keys(rng: np.random.Generator, n: int, max_key: int,
              draws: int) -> np.ndarray:
    acc = np.zeros(n, np.uint32)
    for _ in range(draws):
        acc += rng.integers(0, max_key, n, dtype=np.uint32)
    return acc // np.uint32(draws)


def _quantile_edges(reducers: int, max_key: int, draws: int) -> List[int]:
    total = max_key ** draws
    edges = []
    for r in range(1, reducers):
        lo, hi = 0, max_key  # smallest e with share below e >= r/R
        while lo < hi:
            mid = (lo + hi) // 2
            if _below(mid, max_key, draws) * reducers >= r * total:
                hi = mid
            else:
                lo = mid + 1
        edges.append(lo)
    return edges


def _sampled_edges(traffic: dict, max_key: int, draws: int,
                   maps: int) -> List[int]:
    """``RangePartitioner.sketch`` and ``determineBounds`` with equal
    map weights, as ``[lo, hi)`` edges (a bound plus one)."""
    reducers = int(traffic["reducers"])
    size = min(float(traffic["sample_points_per_reducer"]) * reducers, 1e6)
    per_map = ceil(3.0 * size / maps)
    rng = np.random.default_rng(int(traffic["sample_seed"]))
    sample = np.sort(draw_keys(rng, per_map * maps, max_key, draws))
    step = len(sample) / reducers
    bounds, target, prev = [], step, None
    for i, k in enumerate(sample):
        if len(bounds) == reducers - 1:
            break
        if i + 1 >= target and (prev is None or k > prev):
            bounds.append(int(k))
            prev = k
            target += step
    if len(bounds) != reducers - 1:
        raise ValueError("the sample gave fewer bounds than reducers")
    return [b + 1 for b in bounds]


def edges_and_shares(traffic: dict, max_key: int, maps: int
                     ) -> Tuple[np.ndarray, List[float]]:
    """Strictly increasing ``[reducers - 1]`` uint32 edges (reducer r
    holds ``[edges[r-1], edges[r])``) and each range's share of the
    key distribution."""
    reducers = int(traffic["reducers"])
    draws = DRAWS[traffic["key_distribution"]]
    rule = traffic["edges"]
    if rule == "quantile":
        edges = _quantile_edges(reducers, max_key, draws)
    elif rule == "sampled":
        edges = _sampled_edges(traffic, max_key, draws, maps)
    else:
        raise ValueError(f"unknown edge rule {rule!r}")
    if any(b <= a for a, b in zip(edges, edges[1:])):
        raise ValueError("edges are not strictly increasing")
    total = max_key ** draws
    cum = [0] + [_below(e, max_key, draws) for e in edges] + [total]
    shares = [(b - a) / total for a, b in zip(cum, cum[1:])]
    return np.asarray(edges, np.uint32), shares


def range_counts(n: int, traffic: dict, shares: List[float]) -> np.ndarray:
    """Keys of one map task in each reducer range: an equal split for
    quantile edges, else the shares rounded by largest remainder."""
    reducers = len(shares)
    if traffic["edges"] == "quantile":
        counts = np.full(reducers, n // reducers, np.int64)
        counts[: n % reducers] += 1
        return counts
    exact = np.asarray(shares) * n
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(counts - exact)[: n - int(counts.sum())]] += 1
    return counts


def make_map_keys(max_key: int, draws: int, edges: np.ndarray,
                  counts: np.ndarray, pool: int, seed: int, map_index: int
                  ) -> np.ndarray:
    """One map task's uint32 keys, ``counts[r]`` of them in reducer
    range r: of a pool of ``pool`` random keys, the first of each range
    up to its count, in the pool's order."""
    rng = np.random.default_rng(seed_words(seed, POOL, map_index))
    keys = draw_keys(rng, pool, max_key, draws)
    widths = np.diff(np.concatenate([[0], edges.astype(np.int64),
                                     [max_key]]))
    rid = np.repeat(np.arange(len(counts), dtype=np.uint8), widths)[keys]
    order = np.argsort(rid, kind="stable")  # a radix sort for uint8
    held = np.bincount(rid, minlength=len(counts))
    if np.any(held < counts):
        raise RuntimeError("key pool too small for a range's count")
    keep = np.zeros(pool, bool)
    for s, c in zip(np.cumsum(held) - held, counts):
        keep[order[s: s + c]] = True
    return keys[keep]


def make_input(config: dict, traffic: dict, seed: int):
    """All map tasks' keys (map m's are the m-th equal slice), the
    edges, and each map's count per reducer range."""
    n_keys, max_key = int(config["keys"]), int(config["max_key"])
    n_maps = int(config["maps"])
    if n_keys % n_maps:
        raise ValueError("keys must split evenly over the map tasks")
    if int(traffic["reducers"]) > 256:
        raise ValueError("at most 256 reducers")
    if max_key > 1 << 30:
        raise ValueError("max_key must be at most 2^30")
    edges, shares = edges_and_shares(traffic, max_key, n_maps)
    counts = range_counts(n_keys // n_maps, traffic, shares)
    # a pool large enough that every range holds its count with seven
    # standard deviations to spare
    pool = int(max((c + 7 * c ** 0.5 + 64) / s
                   for c, s in zip(counts, shares) if c))
    draws = DRAWS[traffic["key_distribution"]]
    with ThreadPoolExecutor(n_maps) as ex:
        keys = list(ex.map(lambda m: make_map_keys(
            max_key, draws, edges, counts, pool, seed, m), range(n_maps)))
    return np.concatenate(keys), edges, counts
