"""Seconds of ``ckpt.wait`` spans in the training window: the loop blocked
on pending commits, in practice the final drain of the last save."""

from chipbench.metrics_spans import train_seconds


def read(rec):
    return train_seconds(rec, "ckpt.wait")
