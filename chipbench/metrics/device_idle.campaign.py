"""Share of the traced campaigns' wall time in which no operation ran on
the device."""

from chipbench.trace import busy_within


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("campaign.run_fleet")
    busy, total = busy_within(run.trace, spans)
    if total <= 0:
        return None
    return 100.0 * (1.0 - busy / total)
