"""Readers of the program's own spans on traces made by hand: unions over
overlapping spans, programs at span edges, and nothing read where a
program without those spans leaves only the benchmark's own."""
import pytest

from chipbench import harness as H
from chipbench.spans import WINDOW_SPAN
from chipbench.tests.test_readers import MS, ev, run_of
from chipbench.trace import Trace


def program_trace():
    """Two traced campaigns (0-100 and 200-300 ms) with the program's own
    spans, overlapping where a union must not count twice."""
    tr = Trace(window=(0, 400 * MS))
    tr.spans = [ev(WINDOW_SPAN, 0, 400),
                ev("campaign.run_fleet", 0, 100),
                ev("campaign.run_fleet", 200, 300),
                # workers 10-60 and 50-70 overlap: 60 ms of the first
                # campaign; 210-280 of the second
                ev("campaign.worker", 10, 60), ev("campaign.worker", 50, 70),
                ev("campaign.worker", 210, 280),
                # timed work: probe 10-20 and point 15-25 overlap (15 ms),
                # drift 30-32, point 215-220
                ev("campaign.probe", 10, 20), ev("campaign.point", 15, 25),
                ev("campaign.drift", 30, 32), ev("campaign.point", 215, 220),
                # payload checks: 40-50 nested in 35-55 (20 ms), 230-236
                ev("campaign.payload_check", 35, 55),
                ev("campaign.payload_check", 40, 50),
                ev("campaign.payload_check", 230, 236)]
    tr.spans.sort(key=lambda e: e.start)
    # device busy 12-14 (in the probe only), 24-30 (1 ms of the first
    # point) and 216-218 (2 ms of the second)
    tr.ops[0] = [ev("%spmv_ell_rt.1 custom-call", 12, 14),
                 ev("%copy.1 copy", 24, 30),
                 ev("%spmv_ell_rt.1 custom-call", 216, 218)]
    # programs starting at a check's first and last instant count, one
    # just after it does not, one inside two nested checks counts once
    tr.programs[0] = [ev("jit_add(1)", t, t + 0.1)
                      for t in (35, 45, 55, 55.5, 236, 300)]
    return tr


def test_program_span_readers():
    run = run_of(program_trace(), {})
    read = lambda name: H.load_reader(name)(run)  # noqa: E731
    # probe and point union 15 ms, drift 2 ms, point 5 ms; two campaigns
    assert read("campaign.measure_s") == pytest.approx((15 + 2 + 5) / 2e3)
    assert read("campaign.payload_check_s") == pytest.approx((20 + 6) / 2e3)
    # 35, 45, 55 in the first campaign's checks; 236 in the second's
    assert read("campaign.payload_check.device_programs") == 2
    # 100 - 60 ms and 100 - 70 ms outside the workers
    assert read("campaign.fleet_s") == pytest.approx((40 + 30) / 2e3)
    # points 15-25 and 215-220 hold 15 ms, 3 ms of them busy
    assert read("device_idle.point") == pytest.approx(100 * (1 - 3 / 15))


@pytest.mark.parametrize("name,missing", [
    ("campaign.measure_s", ("campaign.probe", "campaign.point",
                            "campaign.drift")),
    ("campaign.measure_s", ("campaign.run_fleet",)),
    ("campaign.payload_check_s", ("campaign.payload_check",)),
    ("campaign.payload_check.device_programs", ("campaign.payload_check",)),
    ("campaign.fleet_s", ("campaign.worker",)),
    ("campaign.fleet_s", ("campaign.run_fleet",)),
    ("device_idle.point", ("campaign.point",)),
])
def test_a_program_span_reader_without_its_spans_reads_nothing(name,
                                                              missing):
    """A trace of the benchmark's own spans alone, as a program without
    spans of its own leaves, reads nothing."""
    tr = program_trace()
    tr.spans = [s for s in tr.spans if s.name not in missing]
    assert H.load_reader(name)(run_of(tr, {})) is None
