"""Seconds of ``commit.encode`` spans per window commit, summed over
threads: the codec on quantized deltas of tensors under the chunk threshold
(the chunk layer encodes inside ``commit.chunk_stream``, with no span of its
own)."""

from chipbench.metrics_common import per_op


def read(rec):
    return per_op(rec, "commit.encode", "commit")
