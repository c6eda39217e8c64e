"""Seconds of ``chunk.hash`` spans per window commit, summed over threads:
SHA-256 of every chunk as its key and of the stored truth."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.hash", "commit")
