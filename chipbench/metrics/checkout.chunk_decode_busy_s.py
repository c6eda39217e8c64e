"""Seconds of ``chunk.decode`` spans per window checkout, summed over
threads: each chunk's hops decoded and dequantized on the host."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.decode", "checkout")
