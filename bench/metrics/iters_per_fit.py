"""Mean ``n_iter_`` of the window's fits.  Layer: solver loop
(`core/kmeans.py`)."""

from lib import fits

UNIT = "iterations"


def read(run):
    done = fits.done(run)
    return sum(f["n_iter"] for f in done) / len(done) if done else None
