"""Seconds JAX spent lowering and compiling in each campaign of the window
(its monitoring events), averaged over the campaigns."""


def read(run):
    camps = run.counters.get("campaigns") or []
    if not camps:
        return None
    return sum(c["compile_s"] for c in camps) / len(camps)
