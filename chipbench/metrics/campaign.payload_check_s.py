"""Seconds each traced campaign spends in the payload check of its (region,
mode) pairs: the union of the program's ``campaign.payload_check`` spans,
over the number of campaigns traced."""

from chipbench.trace import union


def read(run):
    tr = run.trace
    if tr is None:
        return None
    camps = tr.spans_named("campaign.run_fleet")
    checks = tr.spans_named("campaign.payload_check")
    if not camps or not checks:
        return None
    ns = sum(t - s for s, t in union(checks, float("-inf"), float("inf")))
    return ns * 1e-9 / len(camps)
