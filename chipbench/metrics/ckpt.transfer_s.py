"""Seconds of ``ckpt.transfer`` spans per save in the training window: the
device->host copy of the changed leaves, inside the loop's snapshot stall."""

from chipbench.metrics_spans import TRAIN_ROOT, train_seconds
from chipbench.trace import span_count


def read(rec):
    seconds = train_seconds(rec, "ckpt.transfer")
    saves = span_count(rec["spans"], "ckpt.snapshot", root=TRAIN_ROOT)
    return seconds / saves if seconds is not None and saves else None
