"""Share of the fits' solver loops that the Anderson solve and
m-adjustment take: device time of the leaf operations in the
``repro.aa`` scope (`core/anderson.py`) that start inside each fit's
solver loop, over those loops' device time (the loop `lloyd_roofline`
reads).  Layer: Anderson guard."""

from lib import spans

UNIT = "%"


def read(run):
    return spans.loop_scope_pct(run.trace, "repro.aa")
