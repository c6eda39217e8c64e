"""Seconds of ``chunk.parent`` spans per window commit, summed over
threads: the parent's chunk read and decoded hop by hop on the host."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.parent", "commit")
