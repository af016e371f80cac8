"""Seconds each traced campaign spends building its serve engine: the union
of the program's ``campaign.serve.build`` spans (weights, admission wave,
warm ticks) inside the ``campaign.run_fleet`` spans, over the number of
campaigns traced."""

from chipbench.trace import union


def read(run):
    tr = run.trace
    if tr is None:
        return None
    camps = tr.spans_named("campaign.run_fleet")
    builds = tr.spans_named("campaign.serve.build")
    if not camps or not builds:
        return None
    ns = sum(t - s for c in camps for s, t in union(builds, c.start, c.end))
    return ns * 1e-9 / len(camps)
