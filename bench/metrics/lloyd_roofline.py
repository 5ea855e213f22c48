"""The solver loop's share of its Lloyd steps' roofline:
Σ n_iter_ × (least time of one step's algorithmic work,
`lib.work.lloyd_step`) over the device time of the fits' solver loops in
the trace.  A fit's solver loop is the longest ``while`` operation that
starts inside its ``bench.fit`` span (`core/kmeans.py` runs Algorithm 1
as one ``lax.while_loop``), so k-means++ seeding, the per-fit trace and
compile, and host work are left out.  Layer: step backends
(`core/backends/`), whose steps take nearly all of the loop's time.
"""

from lib import fits, trace

UNIT = "%"


def is_solver_loop(name: str) -> bool:
    return name.startswith("%while")


def read(run):
    loops = trace.longest_in_spans(run.trace, "bench.fit", is_solver_loop)
    done = fits.done(run)
    if run.peaks is None or not loops or None in loops \
            or len(loops) != len(done):
        return None
    iters = sum(f["n_iter"] for f in done)
    return 100.0 * iters * fits.step_least_time(run) / sum(loops)
