"""Share of the time inside the program's ``campaign.point`` spans in which
no operation ran on the device: the host's part of each freshly timed
sweep point, which the host clock counts into t(k)."""

from chipbench.trace import busy_s, union


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    points = union(tr.spans_named("campaign.point"), float("-inf"),
                   float("inf"))
    total = sum(t - s for s, t in points) * 1e-9
    if total <= 0:
        return None
    busy = sum(busy_s(tr, s, t) for s, t in points)
    return 100.0 * (1.0 - busy / total)
