"""Share of the fits' solver loops spent in all-reduce, per chip:
100 × the union of the device intervals of the all-reduce operations
that start inside a fit's solver loop, over that loop's time; averaged
over the window's fits and the chips.  A fit's solver loop on a chip is
the longest ``%while`` operation that starts inside its ``bench.fit``
span on that chip's line (`core/kmeans.py` runs Algorithm 1 as one
``lax.while_loop``), so seeding, the pre-loop step, the closing
evaluation and the per-fit trace and compile are left out.

The operations are told by name, since `lib.trace` keeps no scopes.
XLA names an all-reduce it builds by combining several ``%all-reduce.N``
and one it keeps from a single psum ``%psum.N``; async forms add
``-start``/``-done``.  On four v5e chips the loop's only one in the
trace is ``%all-reduce.3``, the tuple of the step's stats, counts and
energy (the convergence count joins it).  The reader logs the names it
matched.  Layer: distribution
(`core/backends/base.py` ``distribute``, whose psums run under the
device scope ``repro.collective``)."""

import re

from lib import trace

UNIT = "%"
FIT_SPAN = "bench.fit"
COLLECTIVE = re.compile(r"%(all-reduce|psum|pmean)\b")


def is_collective(name: str) -> bool:
    return COLLECTIVE.match(name) is not None


def is_solver_loop(name: str) -> bool:
    return name.startswith("%while")


def loop_shares(rec: dict) -> tuple:
    """(one share per fit and chip, the collective names matched)."""
    w0, w1 = rec["window"]
    fits = [(s, s + d) for name, s, d, _ in rec["host"]
            if name == FIT_SPAN and w0 <= s < w1]
    shares, names = [], set()
    for ops in rec["devices"].values():
        for f0, f1 in fits:
            loops = [(s, s + d) for name, s, d in ops
                     if is_solver_loop(name) and f0 <= s < f1]
            if not loops:
                continue
            l0, l1 = max(loops, key=lambda iv: iv[1] - iv[0])
            inside = [[s, min(s + d, l1)] for name, s, d in ops
                      if is_collective(name) and l0 <= s < l1]
            names.update(name for name, s, _ in ops
                         if is_collective(name) and l0 <= s < l1)
            busy = sum(e - s for s, e in trace._merged(inside))
            shares.append(100.0 * busy / max(l1 - l0, 1))
    return shares, sorted(names)


def read(run):
    shares, names = loop_shares(run.trace)
    if not shares or not names:
        return None
    run.log("collective_pct.meshfit", loops=len(shares),
            ops=",".join(names[:8]))
    return sum(shares) / len(shares)
