"""The assignment kernel's share of its roofline: Σ over the window's
predict calls of the least time of their algorithmic work
(`lib.work.assign_call`) over the kernel's device time in the trace.
Layer: kernel (`kernels/assignment.py`, which the fused backend's
``assign`` runs)."""

from lib import trace, work

UNIT = "%"


def is_kernel(name: str) -> bool:
    """The kernel's custom call takes the name of its jitted wrapper,
    ``kernels.assignment._assignment_call``."""
    return name.startswith("%_assignment_call")


def read(run):
    events = trace.kernel_events(run.trace, is_kernel)
    if run.peaks is None or not events:
        return None
    k = run.config["estimator"]["n_clusters"]
    d = run.config["data"]["d"]
    least = sum(work.least_time(*work.assign_call(c["rows"], k, d),
                                run.peaks) for c in run.calls)
    return 100.0 * least / sum(events)
