"""Mean ``ckpt.snapshot`` span per save: the stall the training loop sees in
``CheckpointManager.save`` (device fingerprints, device->host copy)."""

from chipbench.trace import span_count, span_seconds


def read(rec):
    n = span_count(rec["spans"], "ckpt.snapshot")
    return span_seconds(rec["spans"], "ckpt.snapshot") / n if n else None
