"""Share of the HBM roofline the ``snapshot_fused`` kernel reaches in
commits: p1 and p2 read, q written, over its summed device time."""

from chipbench.metrics_common import roofline


def read(rec):
    return roofline(rec, "snapshot_fused")
