"""Seconds of ``chunk.xdelta`` spans per window commit, summed over
threads: the lossless bit-pattern difference of each changed chunk of a
non-float32 leaf against its parent's truth, and its encode."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.xdelta", "commit")
