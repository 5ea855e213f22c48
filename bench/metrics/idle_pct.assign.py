"""Share of the traced window in which no operation ran on the device:
100·(1 − busy/window), busy being the union of the device's operation
intervals (`lib.trace`).  Layer: device."""

from lib import trace

UNIT = "%"


def read(run):
    return trace.idle_pct(run.trace)
