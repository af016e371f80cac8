"""The reduction from trace to metrics, on events made by hand and on a
trace recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as T
from chipbench.spans import WINDOW_SPAN, Tracer

MS = 1_000_000


def ev(name, start_ms, end_ms, detail=""):
    return T.Event(name, start_ms * MS, end_ms * MS, detail)


@pytest.fixture
def hand():
    tr = T.Trace()
    tr.window = (0, 100 * MS)
    tr.ops[0] = [ev("fusion.1", 0, 10), ev("fusion.2", 5, 19),
                 ev("custom-call.3", 50, 60, "tpu_custom_call"),
                 ev("fusion.1", 95, 110)]
    tr.programs[0] = [ev("jit_lambda(1)", 0, 20), ev("jit_add(2)", 50, 60),
                      ev("jit_lambda(1)", 95, 110)]
    tr.spans = [ev(WINDOW_SPAN, 0, 100), ev("campaign.run_fleet", 18, 70),
                ev("bench.reference", 70, 95), ev("campaign.sweep", 30, 45)]
    tr.spans.sort(key=lambda e: e.start)
    return tr


def test_busy_is_the_union_clipped_to_the_window(hand):
    assert T.busy_s(hand) == pytest.approx(0.034)        # 19 + 10 + 5 ms
    assert T.busy_s(hand, 0, 8 * MS) == pytest.approx(0.008)


def test_gaps_are_attributed_to_the_innermost_open_span(hand):
    # idle: 19-50 (31 ms; middle 34.5 in campaign.sweep), 60-95 (35 ms;
    # middle 77.5 in bench.reference)
    assert T.gaps(hand.ops[0], *hand.window) == [(19 * MS, 50 * MS),
                                                 (60 * MS, 95 * MS)]
    assert T.idle_by_span(hand) == [["bench.reference", pytest.approx(0.035)],
                                    ["campaign.sweep",
                                     pytest.approx(0.031)]]
    assert T.innermost(hand.spans, 5 * MS) == "none"


def test_top_ops(hand):
    assert T.top_ops(hand) == [["fusion.1", pytest.approx(0.015)],
                               ["fusion.2", pytest.approx(0.014)],
                               ["custom-call.3", pytest.approx(0.010)]]


def test_kernel_predicate_and_busy_within(hand):
    assert [e.name for e in hand.ops[0] if T.is_kernel(e)] == \
        ["custom-call.3"]
    busy, total = T.busy_within(hand, hand.spans_named("bench.reference"))
    assert (busy, total) == (pytest.approx(0.0), pytest.approx(0.025))


def test_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = Tracer(True, tmp_path / "trace")
    tracer.start()
    for _ in range(3):
        with tracer.span("campaign.run_fleet"):
            f(x).block_until_ready()
    tracer.stop()
    tr = T.load(tracer.out_dir, "cpu")
    assert len(tr.spans_named("campaign.run_fleet")) == 3
    assert 0 < tr.window_s <= tracer.seconds + 1e-3
    assert tr.ops[0], "the CPU client's operations are read as the device's"
    busy = T.busy_s(tr)
    assert 0 < busy < tr.window_s
    steps = tr.spans_named("campaign.run_fleet")
    assert all(tr.window[0] <= s.start <= s.end <= tr.window[1]
               for s in steps)
