"""The map sort's share of its roofline: 8 bytes per key sorted (one
read, one write) at peak HBM bandwidth, over the device time of
``MapShardSorter``'s jitted step (module ``jit__step``) in the trace."""

from shufflebench.roofline import share_pct, sort_bytes

MODULE = "jit__step"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    keys = len(run.jobs) * int(run.cell.config["keys"])
    least = sort_bytes(keys) / run.peaks["hbm_bytes_per_s"]
    return share_pct(least, run.trace.kernel_s(MODULE))
