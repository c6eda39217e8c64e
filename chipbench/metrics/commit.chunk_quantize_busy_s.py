"""Seconds of ``chunk.quantize`` spans per window commit, summed over
threads: host quantize of each chunk against its parent, and the dequant to
the stored truth."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.quantize", "commit")
