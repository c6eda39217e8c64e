"""The lineage analogue of MFU for a checkout: base, deltas and output
bytes at the chip's peak bandwidth, over ``checkout_s``."""

from chipbench.metrics_common import moved_share


def read(rec):
    return moved_share(rec, "checkout")
