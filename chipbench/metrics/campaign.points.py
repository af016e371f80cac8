"""Sweep points measured in each campaign of the window, counted from its
report's curves, averaged over the campaigns."""


def read(run):
    camps = run.counters.get("campaigns") or []
    if not camps:
        return None
    return sum(c["points"] for c in camps) / len(camps)
