"""Seconds of ``commit.quantize`` spans per window commit, summed over
threads: the ``snapshot_fused`` call with the transfers around it."""

from chipbench.metrics_common import per_op


def read(rec):
    return per_op(rec, "commit.quantize", "commit")
