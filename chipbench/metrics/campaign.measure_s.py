"""Seconds each traced campaign spends timing what its verdict needs: the
union of the program's ``campaign.probe``, ``campaign.point`` and
``campaign.drift`` spans, over the number of campaigns traced."""

from chipbench.trace import union

SPANS = ("campaign.probe", "campaign.point", "campaign.drift")


def read(run):
    tr = run.trace
    if tr is None:
        return None
    camps = tr.spans_named("campaign.run_fleet")
    timed = [s for s in tr.spans if s.name in SPANS]
    if not camps or not timed:
        return None
    ns = sum(t - s for s, t in union(timed, float("-inf"), float("inf")))
    return ns * 1e-9 / len(camps)
