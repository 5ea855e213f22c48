"""Host-to-device rate of the chunk copies of `_chunked_rows_apply`
(`core/api.py`): the bytes of the window's rows (rows·d·itemsize) over
the total time of their copies, each from its ``repro.rows.put`` span's
start to the runtime's report that the transfer completed: the staging
on the host, the relayout into the device's tiled layout and the copy
(`lib.spans.chunk_copies`).  Layer: estimator."""

from lib import spans

UNIT = "GB/s"


def read(run):
    copies = spans.chunk_copies(run.trace)
    if not copies:
        return None
    rows = sum(c["rows"] for c in run.calls)
    seconds = sum(e - s for s, e in copies) / 1e9
    return rows * run.db.shape[1] * run.db.itemsize / seconds / 1e9
