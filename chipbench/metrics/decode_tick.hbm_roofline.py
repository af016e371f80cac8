"""The decode tick's share of its HBM roofline at k=0: the compulsory bytes
of one tick (every weight once, the tokens' embedding rows, and the keys
and values of every live position; ``chipbench.counts_decoder``) over the
HBM bandwidth, divided by the tick's device time a call, read from the
programs that start inside the ``bench.tick_k0`` span, where the runner
calls the decode region's runtime-k build at k=0 right after the traced
campaign (``chipbench.tick_trace.seconds_per_call``)."""

from chipbench.tick_trace import seconds_per_call


def read(run):
    if run.trace is None or not run.peaks or "tick_bytes" not in run.counters:
        return None
    per_call = seconds_per_call(run.trace, "bench.tick_k0")
    if per_call is None:
        return None
    least = run.counters["tick_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / per_call
