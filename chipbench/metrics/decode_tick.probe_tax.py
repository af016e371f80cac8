"""What wrapping the decode tick for a sweep costs at k=0: the device time
a call of the region's runtime-k build at k=0 (programs starting inside
``bench.tick_k0``) over that of the engine's plain tick (inside
``bench.plain_tick``), less 1, in percent
(``chipbench.tick_trace.seconds_per_call``). Both are called on the same
state right after the traced campaign."""

from chipbench.tick_trace import seconds_per_call


def read(run):
    wrapped = seconds_per_call(run.trace, "bench.tick_k0")
    plain = seconds_per_call(run.trace, "bench.plain_tick")
    if wrapped is None or plain is None:
        return None
    return 100.0 * (wrapped / plain - 1.0)
