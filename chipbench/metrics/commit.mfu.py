"""The lineage analogue of MFU for a commit: the bytes a commit of one
derivative must move on the chip whatever implements it (parent and child
read, an int8 delta written), at the chip's peak bandwidth, over
``commit_s``."""

from chipbench.metrics_common import moved_share


def read(rec):
    return moved_share(rec, "commit")
