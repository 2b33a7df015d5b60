"""Share (%) of the traced span in which the device ran no op while the
engine was in a host phase other than ``wait_arrival``: idle the host's
own work caused, per chip, averaged over the chips (``phases.py``)."""

from chipbench import phases


def read(rec):
    return phases.host_idle_pct(rec)
