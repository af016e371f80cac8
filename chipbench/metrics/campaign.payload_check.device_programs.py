"""Device programs each traced campaign starts inside the program's
``campaign.payload_check`` spans: the static-k run, the oracle's eager
operations and the reference's run, counted where they are dispatched. A
program counts once, however many spans hold its start."""


def read(run):
    tr = run.trace
    if tr is None or not tr.programs:
        return None
    camps = tr.spans_named("campaign.run_fleet")
    checks = tr.spans_named("campaign.payload_check")
    if not camps or not checks:
        return None
    dev = min(tr.programs)
    n = sum(1 for e in tr.programs[dev]
            if any(s.start <= e.start <= s.end for s in checks))
    return n / len(camps)
