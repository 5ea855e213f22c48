"""95th percentile of how late the load generator sent each request
(send time − due time).  Layer: the benchmark's load generator; a
starved generator must not read as a fast server."""

import numpy as np

UNIT = "ms"


def read(run):
    lag = (run.sent - run.due)[~np.isnan(run.sent)]
    return float(np.percentile(lag, 95) * 1e3) if lag.size else None
