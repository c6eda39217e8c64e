"""Share of the traced lineage window in which no operation ran on the
device."""

from chipbench.metrics_common import idle_share


def read(rec):
    return idle_share(rec)
