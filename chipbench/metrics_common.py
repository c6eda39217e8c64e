"""Arithmetic the per-layer readers in ``chipbench/metrics`` share."""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench.trace import span_seconds


def roofline(rec: Dict[str, Any], kernel: str) -> Optional[float]:
    """Percent of the HBM roofline: the least time the kernel's bytes take
    at peak bandwidth over its summed device time. None where the kernel did
    not run or moved nothing."""
    seconds = rec["device"]["kernel_s"].get(kernel, 0.0)
    moved = rec["kernel_bytes"].get(kernel, 0)
    if seconds <= 0 or moved <= 0:
        return None
    return 100.0 * moved / rec["peaks"]["hbm_bytes_per_s"] / seconds


def idle_share(rec: Dict[str, Any]) -> Optional[float]:
    dev = rec["device"]
    if dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def per_op(rec: Dict[str, Any], span: str, op: str) -> Optional[float]:
    """Seconds of ``span`` under the window's ``op`` operations, per op."""
    n = rec["record"].get(op + "s", 0)
    if not n:
        return None
    return span_seconds(rec["spans"], span, root="chipbench." + op) / n


def moved_share(rec: Dict[str, Any], op: str) -> Optional[float]:
    """Percent: bytes the window's ``op`` operations had to move at peak
    bandwidth, over the seconds they took."""
    n = rec["record"].get(op + "s", 0)
    seconds = rec["e2e"].get(op + "_s")
    if not n or not seconds:
        return None
    moved = rec["record"][op + "_moved"] / n
    return 100.0 * moved / rec["peaks"]["hbm_bytes_per_s"] / seconds
