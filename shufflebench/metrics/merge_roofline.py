"""The reduce merge's share of its roofline: 8 bytes per key merged at
peak HBM bandwidth, over the device time of the benchmark's merge jit
(``shufflebench_merge``, around ``ops/sort.merge_received``)."""

from shufflebench.reference import TOTAL
from shufflebench.roofline import merge_bytes, share_pct

MODULE = "shufflebench_merge"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    keys = sum(int(d[TOTAL]) for j in run.jobs for d in j.digests.values())
    least = merge_bytes(keys) / run.peaks["hbm_bytes_per_s"]
    return share_pct(least, run.trace.kernel_s(MODULE))
