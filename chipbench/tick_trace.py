"""Device time of the calls a runner makes inside a span of its own, from
a reduced trace (``chipbench.trace``)."""
from __future__ import annotations

from collections import Counter


def seconds_per_call(tr, span_name: str):
    """Device seconds a call, on the first device, of the calls made inside
    the ``span_name`` spans; None where no program starts inside one.

    The calls are counted in the trace, not taken from the runner: every
    call runs the same programs, so the program names that start most often
    inside the span are the parts of one call, and a call's time is their
    total over that count. A call that runs as two programs counts once,
    and a program the trace places across the span's edge (on the chip,
    device programs show up to about 0.5 ms before the host span that made
    them) changes the count or drops out, instead of skewing the time."""
    if tr is None or not tr.programs:
        return None
    spans = tr.spans_named(span_name)
    dev = min(tr.programs)
    progs = [p for p in tr.programs[dev]
             if any(s.start <= p.start <= s.end for s in spans)]
    if not progs:
        return None
    counts = Counter(p.name for p in progs)
    calls = max(counts.values())
    return sum(p.seconds for p in progs if counts[p.name] == calls) / calls
