"""Seconds of ``checkout.param`` spans per window checkout, summed over
threads (only those under a checkout, not a commit's parent reads)."""

from chipbench.metrics_common import per_op


def read(rec):
    return per_op(rec, "checkout.param", "checkout")
