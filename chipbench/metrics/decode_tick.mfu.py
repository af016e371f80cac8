"""The decode tick's share of the chip's bfloat16 peak at k=0: the
operations of one tick (two a matrix weight a token, and the attention's
scores and weighted values over every live position;
``chipbench.counts_decoder``) over the peak, divided by the tick's device
time a call, as ``decode_tick.hbm_roofline`` reads it."""

from chipbench.tick_trace import seconds_per_call


def read(run):
    if run.trace is None or not run.peaks or "tick_flops" not in run.counters:
        return None
    per_call = seconds_per_call(run.trace, "bench.tick_k0")
    if per_call is None:
        return None
    least = run.counters["tick_flops"] / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / per_call
