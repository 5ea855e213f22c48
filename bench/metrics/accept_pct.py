"""Share of iterations whose accelerated iterate the energy guard kept:
100·Σ n_accepted_ / Σ n_iter_.  Layer: Anderson guard
(`core/anderson.py`, `core/kmeans.py`)."""

from lib import fits

UNIT = "%"


def read(run):
    done = fits.done(run)
    iters = sum(f["n_iter"] for f in done)
    return 100.0 * sum(f["n_accepted"] for f in done) / iters if iters \
        else None
