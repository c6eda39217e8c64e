"""Seconds of ``commit.chunk_stream`` spans per window commit, summed over
threads: the host chunk layer (tensors of 8 MiB or more)."""

from chipbench.metrics_common import per_op


def read(rec):
    return per_op(rec, "commit.chunk_stream", "commit")
