"""Readers of program spans that an older program does not open.

A program without the span reports nothing (``None``), so the result line
leaves the metric out instead of reading 0.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench.metrics_common import per_op
from chipbench.trace import span_count, span_seconds

TRAIN_ROOT = "chipbench.train"


def per_op_opened(rec: Dict[str, Any], span: str, op: str
                  ) -> Optional[float]:
    """Seconds of ``span`` under the window's ``op`` operations, per op,
    summed over threads; None where no such span was opened."""
    if not span_count(rec["spans"], span, root="chipbench." + op):
        return None
    return per_op(rec, span, op)


def train_seconds(rec: Dict[str, Any], span: str) -> Optional[float]:
    """Seconds of ``span`` in the training window, summed over threads;
    None where no such span was opened."""
    if not span_count(rec["spans"], span, root=TRAIN_ROOT):
        return None
    return span_seconds(rec["spans"], span, root=TRAIN_ROOT)
