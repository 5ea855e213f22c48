"""Share of the rows the server computed that were padding:
Σ padded_rows / Σ (batch_rows + padded_rows), from its metrics.  Layer:
serving worker (`serving/server.py`)."""

UNIT = "%"


def read(run):
    rows = sum(r["batch_rows"] for _, r in run.sink.records)
    pad = sum(r["padded_rows"] for _, r in run.sink.records)
    return 100.0 * pad / (rows + pad) if rows else None
