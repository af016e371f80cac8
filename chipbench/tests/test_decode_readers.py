"""The serve cell's per-layer readers on a trace made by hand."""
import pytest

from chipbench import harness as H
from chipbench.peaks import peaks_for
from chipbench.spans import WINDOW_SPAN
from chipbench.trace import Event, Trace

MS = 1_000_000
PEAKS = peaks_for("TPU v5 lite")
COUNTS = {"tick_bytes": 9_126_123_520, "tick_flops": 37_038_260_224}


def ev(name, a, b):
    return Event(name, a * MS, b * MS)


def serve_trace():
    """Two campaigns, each building its engine for 300 ms (the second in
    two stretches, one cut by the campaign's end), a build outside any
    campaign, then three wrapped k=0 ticks of 40 ms and three plain ticks
    of 32 ms; a program that starts before ``bench.tick_k0`` is left
    out."""
    tr = Trace(window=(0, 10_000 * MS))
    tr.spans = [ev(WINDOW_SPAN, 0, 10_000),
                ev("campaign.run_fleet", 0, 4000),
                ev("campaign.serve.build", 100, 400),
                ev("campaign.run_fleet", 4000, 8000),
                ev("campaign.serve.build", 4100, 4300),
                ev("campaign.serve.build", 4250, 4350),
                ev("campaign.serve.build", 7950, 8100),
                ev("campaign.serve.build", 8200, 8900),
                ev("bench.tick_k0", 9000, 9500),
                ev("bench.plain_tick", 9500, 9900)]
    tr.programs[0] = [ev("jit_noisy", 8990, 9030)] \
        + [ev("jit_noisy", t, t + 40) for t in (9010, 9100, 9200)] \
        + [ev("jit_tick", t, t + 32) for t in (9600, 9700, 9800)]
    tr.ops[0] = [ev("%fusion.1 = bf16[4] fusion()", p.start / MS,
                    p.end / MS) for p in tr.programs[0]]
    return tr


def read(name, tr, counters=COUNTS):
    return H.load_reader(name)(H.Run(tr, dict(counters), {}, {}, PEAKS))


def test_tick_roofline_and_mfu_read_the_k0_ticks():
    tr = serve_trace()
    # 9.126 GB at 819 GB/s = 11.143 ms, over 40 ms a tick
    assert read("decode_tick.hbm_roofline", tr) == pytest.approx(
        100 * 9_126_123_520 / 819e9 / 0.040)
    # 37.04 GFLOP at 197 TFLOP/s = 0.188 ms, over 40 ms
    assert read("decode_tick.mfu", tr) == pytest.approx(
        100 * 37_038_260_224 / 197e12 / 0.040)


def test_a_call_that_runs_as_two_programs_counts_once():
    """Each wrapped tick split into a 30 ms and a 10 ms program reads as the
    one 40 ms tick it is."""
    tr, split = serve_trace(), serve_trace()
    split.programs[0] = [ev("jit_noisy", 8990, 9030)] + [
        ev(name, t + a, t + b) for t in (9010, 9100, 9200)
        for name, a, b in (("jit_gather", 0, 30), ("jit_noisy", 30, 40))] \
        + [ev("jit_tick", t, t + 32) for t in (9600, 9700, 9800)]
    for name in ("decode_tick.hbm_roofline", "decode_tick.mfu",
                 "decode_tick.probe_tax"):
        assert read(name, split) == pytest.approx(read(name, tr))


def test_programs_at_a_span_edge_change_nothing():
    """As on the chip, the trace puts the first wrapped call's program just
    before ``bench.tick_k0`` opens, and the plain tick's warm-up call just
    before it closes: a call fewer, a neighbour more, the same reading."""
    tr, edged = serve_trace(), serve_trace()
    edged.programs[0] = [ev("jit_noisy", 8999.5, 9039.5)] \
        + [ev("jit_noisy", t, t + 40) for t in (9100, 9200)] \
        + [ev("jit_tick", 9499.6, 9531.6)] \
        + [ev("jit_tick", t, t + 32) for t in (9600, 9700, 9800)]
    for name in ("decode_tick.hbm_roofline", "decode_tick.mfu",
                 "decode_tick.probe_tax"):
        assert read(name, edged) == pytest.approx(read(name, tr))


def test_probe_tax_is_the_wrapped_tick_over_the_plain_one():
    assert read("decode_tick.probe_tax", serve_trace()) == pytest.approx(
        100 * (40 / 32 - 1))


def test_serve_build_seconds_count_inside_campaigns_only():
    # 300 ms, then the union 200 + 50 ms and 50 ms up to the end: 600 / 2
    assert read("campaign.serve_build_s", serve_trace()) == pytest.approx(
        0.3)


@pytest.mark.parametrize("name", ["decode_tick.hbm_roofline",
                                  "decode_tick.mfu", "decode_tick.probe_tax",
                                  "campaign.serve_build_s"])
def test_a_trace_of_another_program_reads_nothing(name):
    """The parent's campaign spans, without the serve spans or the tick
    calls, give no reading."""
    tr = serve_trace()
    tr.spans = [s for s in tr.spans
                if s.name in (WINDOW_SPAN, "campaign.run_fleet")]
    assert read(name, tr) is None
