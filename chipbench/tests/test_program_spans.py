"""The program's own spans (``repro.spans``) on a traced tiny campaign on
the CPU: each lands inside the benchmark's ``campaign.run_fleet``, nested
as the calls are, with its metadata; with the profiler off they record
nothing."""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from chipbench import trace as T
from chipbench.spans import WINDOW_SPAN, Tracer
from chipbench.tests.tiny import DATA

# every span the campaign path opens
PROGRAM_SPANS = frozenset({
    "campaign.fleet.audit", "campaign.fleet.launch", "campaign.worker",
    "campaign.region", "campaign.sweep", "campaign.probe", "campaign.point",
    "campaign.drift", "campaign.payload_check",
    "campaign.payload_check.static_run", "campaign.payload_check.oracle",
    "campaign.payload_check.reference", "campaign.body_size",
    "campaign.fleet.merge", "campaign.fleet.classify",
    "campaign.fleet.report"})
# a Pallas region states its body size, so a campaign over one never
# derives it (test_body_size_is_spanned_only_where_derived)
NOT_IN_A_PALLAS_CAMPAIGN = {"campaign.body_size"}


def span_stats(out_dir) -> dict:
    """{span name: the stats of each of its events, in start order} for the
    host events named ``campaign.*``."""
    pd = ProfileData.from_file(T.find_xplane(out_dir))
    found: dict = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("campaign."):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return {name: [s for _, s in sorted(evs, key=lambda f: f[0])]
            for name, evs in found.items()}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One in-process campaign of the tiny cell's shape, traced under the
    benchmark's tracer: (trace, span stats, fleet result)."""
    from repro.fleet.executor import run_fleet
    from repro.fleet.launchers import LocalLauncher
    from repro.fleet.plan import SweepPlan, TargetSpec

    tmp = tmp_path_factory.mktemp("spans")
    cfg = json.loads((DATA / "tiny-spmxv.json").read_text())
    mix = json.loads((DATA / "tiny-campaign.json").read_text())
    params = {"kernel": cfg["kernel"], "sizes": [cfg["rows"]],
              "qs": cfg["qs"], "nnz_per_row": cfg["nnz_per_row"],
              "br": cfg["block_rows"], "seed": 2 ** 31 + 5}
    plan = SweepPlan(name="spans", store=str(tmp / "spans.jsonl"),
                     targets=[TargetSpec("pallas", tuple(mix["modes"]),
                                         params)],
                     reps=mix["reps"], shards=1, backend=mix["backend"])
    plan.save(str(tmp / "spans.plan.json"))
    tracer = Tracer(True, tmp / "trace")
    tracer.start()
    with tracer.span("campaign.run_fleet"):
        res = run_fleet(str(tmp / "spans.plan.json"), fresh=True,
                        launcher=LocalLauncher(in_process=True))
    tracer.stop()
    return T.load(tracer.out_dir, "cpu"), span_stats(tracer.out_dir), res


def inside(a, b) -> bool:
    return b.start <= a.start and a.end <= b.end


def test_every_span_lands_inside_the_campaign(traced):
    tr, _, _ = traced
    names = {s.name for s in tr.spans} - {WINDOW_SPAN, "campaign.run_fleet"}
    assert names == PROGRAM_SPANS - NOT_IN_A_PALLAS_CAMPAIGN
    (camp,) = tr.spans_named("campaign.run_fleet")
    assert all(inside(s, camp) for s in tr.spans
               if s.name in PROGRAM_SPANS)


def test_spans_nest_as_the_calls_do(traced):
    tr, _, _ = traced

    def each_within(child, *parents):
        outer = [s for p in parents for s in tr.spans_named(p)]
        kids = tr.spans_named(child)
        assert kids and all(any(inside(k, p) for p in outer)
                            for k in kids), (child, parents)

    each_within("campaign.sweep", "campaign.worker",
                "campaign.fleet.classify")
    for timed in ("campaign.probe", "campaign.point", "campaign.drift",
                  "campaign.payload_check"):
        each_within(timed, "campaign.sweep")
    for part in ("static_run", "oracle", "reference"):
        each_within(f"campaign.payload_check.{part}",
                    "campaign.payload_check")
    # the worker's slice and the classify replay each resolve the plan
    each_within("campaign.region", "campaign.run_fleet")
    assert any(inside(r, w) for r in tr.spans_named("campaign.region")
               for w in tr.spans_named("campaign.worker"))


def test_point_spans_are_the_reports_points(traced):
    tr, stats, res = traced
    ks = sorted(k for rep in res.reports.values()
                for r in rep.results.values() for k in r.curve.ks)
    assert len(tr.spans_named("campaign.point")) == len(ks)
    assert sorted(s["k"] for s in stats["campaign.point"]) == ks
    # four pairs measured by the worker, then replayed by the classify step
    sweeps = stats["campaign.sweep"]
    assert [bool(s["replayed"]) for s in sweeps] == [False] * 4 + [True] * 4
    assert {s["mode"] for s in stats["campaign.payload_check"]} == \
        {"fp", "vmem"}
    (merge,) = stats["campaign.fleet.merge"]
    assert merge["records_out"] > 0


def test_body_size_is_spanned_only_where_derived(tmp_path):
    from repro.core import Campaign
    from repro.core.controller import RegionTarget

    target = RegionTarget(name="affine",
                          build=lambda m, k: jax.jit(lambda x: x * 2 + 1),
                          args_for=lambda m, k: (jnp.ones(8),))
    camp = Campaign(str(tmp_path / "store.jsonl"))
    tracer = Tracer(True, tmp_path / "trace")
    tracer.start()
    camp._body_size(target)
    camp._body_size(target)         # the second comes from the store
    tracer.stop()
    camp.store.close()
    tr = T.load(tracer.out_dir, "cpu")
    assert len(tr.spans_named("campaign.body_size")) == 1


def test_spans_with_the_profiler_off_record_nothing(tmp_path):
    from repro.spans import span

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("campaign.point", k=3, reps=2) as sp:
        sp.set_metadata(replayed=True)
        jnp.ones(4).block_until_ready()
    tracer = Tracer(True, tmp_path / "trace")
    tracer.start()
    tracer.stop()
    tr = T.load(tracer.out_dir, "cpu")
    assert [s.name for s in tr.spans] == [WINDOW_SPAN]
