"""Share of the traced window in which the device sat idle while the
host waited for a chunk's answer in `_chunked_rows_apply`
(`core/api.py`): the part of each ``repro.rows.fetch`` span after the
chunk's rows reached the device, which holds the program's launch and
run and the answer's copy back (`lib.spans.fetch_waits`).  The time
before that is `idle_put_pct.assign`'s.  100·idle in those intervals /
window, the device put on the host's clock (`lib.spans.aligned`).
Layer: estimator."""

from lib import spans

UNIT = "%"


def read(run):
    return spans.idle_pct_in(run, spans.fetch_waits(run.trace))
