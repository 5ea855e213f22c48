"""Share of the traced window in which the device sat idle while a chunk
of `_chunked_rows_apply` (`core/api.py`) was on its way to the device:
from each ``repro.rows.put`` span's start, where the host stages the
chunk, to the runtime's report that its transfer completed, past the
relayout into the device's tiled layout and the copy itself
(`lib.spans.chunk_copies`).  100·idle in those intervals / window, the
device put on the host's clock (`lib.spans.aligned`).  Layer:
estimator."""

from lib import spans

UNIT = "%"


def read(run):
    return spans.idle_pct_in(run, spans.chunk_copies(run.trace))
