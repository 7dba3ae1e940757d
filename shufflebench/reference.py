"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program: it sorts the same input
with ``np.sort`` and cuts it at the job's own edges. It runs once the
window has closed. Every reducer of every job in the window is held to
the guarantee the configuration states:

- ``reducers_wrong``: the reducers whose merged output, by the digest
  the device took of it when it was produced, differs from the
  reference's range in any way: the count the merge reports, the keys
  counted in the output, a key past that count, the sum or the xor of
  the keys, a least or largest key outside the range, or a key below
  its neighbour. A key lost, doubled, altered, sent to the wrong
  reducer or out of order moves it.
- ``keys_wrong``: for the reducers of the last job drawn from the seed
  (an eighth of them, at least one), the positions at which the merged
  output read back from the device after the window differs from the
  reference's sorted range, a missing or extra position counting as
  wrong.

Both are exact comparisons; each limit is 0.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LIMITS = {"reducers_wrong": 0, "keys_wrong": 0}
# the digest's fields, as shufflebench_digest stacks them
TOTAL, KEYS, STRAY, SUM, XOR, LEAST, LARGEST, DESCENTS = range(8)


def positions_wrong(got: np.ndarray, want: np.ndarray) -> int:
    n = min(len(got), len(want))
    return int(np.count_nonzero(got[:n] != want[:n])) + abs(
        len(got) - len(want))


def expected_digest(part: np.ndarray) -> Dict[int, int]:
    """What the fields of a sound digest of one reducer whose range
    holds the keys ``part`` read (the least and largest key are checked
    against the range's ends)."""
    n = len(part)
    return {TOTAL: n, KEYS: n, STRAY: 0,
            SUM: int(part.sum(dtype=np.uint64)) & 0xFFFFFFFF,
            XOR: int(np.bitwise_xor.reduce(part)) if n else 0,
            DESCENTS: 0}


def digest_wrong(d: np.ndarray, want: Dict[int, int], lo: int,
                 hi: int) -> bool:
    if any(int(d[f]) != v for f, v in want.items()):
        return True
    return want[TOTAL] > 0 and not lo <= int(d[LEAST]) <= int(
        d[LARGEST]) < hi


def compare(keys: np.ndarray, jobs: List) -> Dict[str, object]:
    """The numbers compared, each with its limit, and which reduce
    tasks failed."""
    ref = np.sort(keys)
    reducers_wrong = keys_wrong = failed = checked = 0
    wants = {}  # by edges: every job of a run has the same
    for job in jobs:
        edges = [int(e) for e in job.edges]
        lows, highs = [0] + edges, edges + [2 ** 32]
        bounds = np.concatenate(
            [[0], np.searchsorted(ref, job.edges, side="left"), [len(ref)]])
        parts = [ref[bounds[r]: bounds[r + 1]] for r in range(len(lows))]
        if tuple(edges) not in wants:
            wants[tuple(edges)] = [expected_digest(p) for p in parts]
        for r, part in enumerate(parts):
            d = job.digests.get(r)
            off = d is None or digest_wrong(
                d, wants[tuple(edges)][r], lows[r], highs[r])
            wrong = 0
            if r in job.kept:
                checked += 1
                wrong = positions_wrong(job.kept[r], part)
            reducers_wrong += off
            keys_wrong += wrong
            failed += bool(off or wrong)
    checks = {"reducers_wrong": reducers_wrong, "keys_wrong": keys_wrong}
    return {
        "correct": all(checks[k] <= LIMITS[k] for k in checks),
        "failed": failed,
        "checked_outputs": checked,
        "checks": {k: {"value": v, "limit": LIMITS[k]}
                   for k, v in checks.items()},
    }
