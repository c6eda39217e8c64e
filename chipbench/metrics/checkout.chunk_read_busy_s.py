"""Seconds of ``chunk.read`` spans per window checkout, summed over
threads: each chunk's base object and hop blobs read from the CAS."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.read", "checkout")
