"""Share of the fits' solver loops that the backend step takes: device
time of the leaf operations in the ``repro.step`` scope
(`core/backends/base.py`) that start inside each fit's solver loop, over
those loops' device time.  The loop is the one `lloyd_roofline` reads:
the longest ``while`` that starts inside each ``bench.fit`` span.
Layer: step backends."""

from lib import spans

UNIT = "%"


def read(run):
    return spans.loop_scope_pct(run.trace, "repro.step")
