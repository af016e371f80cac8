"""Per-layer readers on traces made by hand: what each reads, and that a
reader with nothing to read returns nothing."""
import pytest

from chipbench import harness as H
from chipbench.peaks import peaks_for
from chipbench.spans import WINDOW_SPAN
from chipbench.trace import Event, Trace

MS = 1_000_000
PEAKS = peaks_for("TPU v5 lite")


def ev(name, a, b):
    return Event(name, a * MS, b * MS)


def campaign_trace():
    tr = Trace(window=(0, 1000 * MS))
    tr.spans = [ev(WINDOW_SPAN, 0, 1000), ev("campaign.run_fleet", 0, 800),
                ev("bench.kernel_k0", 900, 1000)]
    kern = "%_lambda_.1 = (f32[8,128]) custom-call(f32[16384,16] %a)"
    # each k=0 call runs its kernel as two events of 1 ms; the first call's
    # program starts before the span on the trace's clock and is left out
    tr.ops[0] = [Event(kern, t * MS, (t + 3) * MS) for t in (10, 20, 30)] \
        + [Event("%copy.1 = f32[8] copy(f32[8] %x)", 40 * MS, 41 * MS)] \
        + [Event(kern, t * MS, (t + 1) * MS)
           for t in (899, 900, 910, 911, 920, 921)]
    tr.programs[0] = [ev("jit_lambda(1)", t, t + 3) for t in (10, 20, 30)] \
        + [ev("jit_add(2)", 40 + i, 40.5 + i) for i in range(7)] \
        + [ev("jit_lambda(1)", t, t + 2) for t in (899, 910, 920)]
    return tr


def run_of(tr, counters, config=None, peaks=PEAKS):
    return H.Run(tr, counters, config or {}, {}, peaks)


def test_campaign_readers():
    tr = campaign_trace()
    c = {"rows": 16384, "nnz_per_row": 16,
         "campaigns": [{"compile_s": 0.5, "points": 48},
                       {"compile_s": 0.7, "points": 46}]}
    read = lambda name: H.load_reader(name)(run_of(tr, c))  # noqa: E731
    assert read("campaign.device_programs") == 10
    assert read("campaign.compile_s") == pytest.approx(0.6)
    assert read("campaign.points") == 47
    # 2,228,224 bytes at 819 GB/s = 2.7206 us, over 2 ms a call
    assert read("spmxv_roofline") == pytest.approx(
        100 * 2_228_224 / 819e9 / 2e-3)
    # busy 9 + 1 ms of 800 ms
    assert read("device_idle.campaign") == pytest.approx(100 * (1 - 10 / 800))


@pytest.mark.parametrize("name", sorted(
    p.stem for p in H.METRICS_DIR.glob("*.py")))
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = run_of(None, {}, peaks={})
    assert H.load_reader(name)(empty) is None
    assert H.load_reader(name)(run_of(Trace(), {})) is None
