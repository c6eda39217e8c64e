"""Share of the HBM roofline the ``fingerprint`` kernel reaches: the bytes
its calls move (``chipbench/costs.py``) over its summed device time in the
trace, against the chip's peak bandwidth."""

from chipbench.metrics_common import roofline


def read(rec):
    return roofline(rec, "fingerprint")
