"""Seconds of each traced campaign that the fleet spends outside its
workers: ``campaign.run_fleet`` time not covered by the program's
``campaign.worker`` spans (audit, launch bookkeeping, merge, classify
replay, report), over the number of campaigns traced."""

from chipbench.trace import busy_ns


def read(run):
    tr = run.trace
    if tr is None:
        return None
    camps = tr.spans_named("campaign.run_fleet")
    workers = tr.spans_named("campaign.worker")
    if not camps or not workers:
        return None
    ns = sum((c.end - c.start) - busy_ns(workers, c.start, c.end)
             for c in camps)
    return ns * 1e-9 / len(camps)
