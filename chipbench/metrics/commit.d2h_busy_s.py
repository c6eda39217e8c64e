"""Seconds of ``commit.d2h`` spans per window commit, summed over threads:
the derivative's leaves copied device->host (chunked leaves whole, the
others one by one in the delta workers)."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "commit.d2h", "commit")
