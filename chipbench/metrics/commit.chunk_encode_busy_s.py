"""Seconds of ``chunk.encode`` spans per window commit, summed over
threads: the codec on each chunk's quantized delta."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.encode", "commit")
