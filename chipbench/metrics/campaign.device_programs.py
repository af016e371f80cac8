"""Device programs executed per traced campaign: every jitted call and every
eager operation the campaign dispatches counts one (the trace's program
events inside the campaign's span). The kernel calls a sweep times are a
few hundred of them; the rest is host-driven work that the device runs one
small program at a time."""


def read(run):
    tr = run.trace
    if tr is None or not tr.programs:
        return None
    spans = tr.spans_named("campaign.run_fleet")
    if not spans:
        return None
    dev = min(tr.programs)
    n = sum(1 for e in tr.programs[dev]
            if any(s.start <= e.start <= s.end for s in spans))
    return n / len(spans)
