"""Seconds of ``chunk.write`` spans per window commit: chunk objects and
delta blobs written to the CAS, one after another on the committing thread."""

from chipbench.metrics_spans import per_op_opened


def read(rec):
    return per_op_opened(rec, "chunk.write", "commit")
