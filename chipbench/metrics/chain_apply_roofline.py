"""Share of the HBM roofline the ``chain_apply`` kernel reaches in
checkouts: base and hops read, the tensor written, over its device time."""

from chipbench.metrics_common import roofline


def read(rec):
    return roofline(rec, "chain_apply")
