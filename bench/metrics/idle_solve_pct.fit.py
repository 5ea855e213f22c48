"""Share of the traced window in which the device sat idle while the
host was inside ``repro.fit.solve``: the per-fit ``jax.jit`` build,
trace, lowering, cache read and dispatch of `AAKMeans.fit`
(`core/api.py`).  100·idle under the span / window, the device put on
the host's clock (`lib.spans.aligned`).  Layer: estimator."""

from lib import spans

UNIT = "%"


def read(run):
    return spans.idle_pct_in(run, spans.host_spans(run.trace,
                                                   "repro.fit.solve"))
