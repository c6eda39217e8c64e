"""Mean ``ckpt.commit`` span per save that landed in the window (the
worker's whole commit: step-delta encode, CAS, lineage)."""

from chipbench.trace import span_count, span_seconds


def read(rec):
    n = span_count(rec["spans"], "ckpt.commit")
    return span_seconds(rec["spans"], "ckpt.commit") / n if n else None
