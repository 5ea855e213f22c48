"""Mean time the server's worker spent on one micro-batch, from its
``serve_latency_s`` metric.  Layer: serving worker
(`serving/server.py`)."""

UNIT = "ms"


def read(run):
    lat = [r["serve_latency_s"] for _, r in run.sink.records]
    return 1e3 * sum(lat) / len(lat) if lat else None
