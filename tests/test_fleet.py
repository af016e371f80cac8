"""Fleet orchestrator: SweepPlan round-trip and grid identity, shard
completeness queries, executor resume/crash-heal semantics, and the
acceptance run — N=2 shards produce a classification byte-identical to a
single-process run, and a completed fleet replays with ZERO measurements.

Measurement determinism: these tests set REPRO_SYNTH_MEASURE, the
deterministic stand-in clock in ``repro.core.absorption.measure``, so
independently-run processes (and shards) produce byte-comparable stores."""
import json
import os

import pytest

from repro.core import CampaignStore, PairStatus
from repro.fleet.executor import (FleetError, FleetState, _incomplete_shards,
                                  in_process_launcher, report_json,
                                  run_fleet, run_worker)
from repro.fleet.plan import PlanError, SweepPlan, TargetSpec


@pytest.fixture
def synth_measure(monkeypatch):
    monkeypatch.setenv("REPRO_SYNTH_MEASURE", "1e-3")


def _plan(tmp_path, *, shards=2, modes=("fp", "mxu"), sizes=(8,),
          name="fleet_probe", stem="fleet"):
    plan = SweepPlan(
        name=name, store=str(tmp_path / stem / "store.jsonl"),
        targets=[TargetSpec("pallas", tuple(modes),
                            {"kernel": "probe", "sizes": list(sizes)})],
        reps=2, shards=shards, backend="interpret")
    path = str(tmp_path / f"{stem}_plan.json")
    plan.save(path)
    return plan, path


# ---------------------------------------------------------------------------
# SweepPlan: serialization, identity, grid enumeration
# ---------------------------------------------------------------------------

def test_plan_round_trip_and_digest(tmp_path):
    plan, path = _plan(tmp_path, sizes=(8, 16))
    loaded = SweepPlan.load(path)
    assert loaded.to_dict() == plan.to_dict()
    assert loaded.digest() == plan.digest()
    # the digest pins content: ANY settings change (here reps) changes it
    loaded.reps = 3
    assert loaded.digest() != plan.digest()


def test_plan_grid_spans_family_and_orders_canonically(tmp_path):
    plan, _ = _plan(tmp_path, modes=("fp", "mxu"), sizes=(8, 16))
    grid = plan.grid()
    assert grid == [("pallas_probe_s8", "fp"), ("pallas_probe_s8", "mxu"),
                    ("pallas_probe_s16", "fp"), ("pallas_probe_s16", "mxu")]
    # worker i of N takes every N-th pair; slices partition the grid
    slices = [grid[i::3] for i in range(3)]
    assert sorted(p for s in slices for p in s) == sorted(grid)


def test_plan_validation_errors(tmp_path):
    with pytest.raises(PlanError, match="no targets"):
        SweepPlan(name="x", store="s", targets=[]).validate()
    with pytest.raises(PlanError, match="unknown pallas kernel"):
        SweepPlan(name="x", store="s", targets=[
            TargetSpec("pallas", ("fp",), {"kernel": "nope", "sizes": [8]})
        ]).validate()
    with pytest.raises(PlanError, match="supports modes"):
        SweepPlan(name="x", store="s", targets=[
            TargetSpec("pallas", ("mxu",), {"kernel": "spmxv", "sizes": [256]})
        ]).validate()
    with pytest.raises(PlanError, match="unknown target kind"):
        SweepPlan(name="x", store="s",
                  targets=[TargetSpec("what", ("fp",), {})]).validate()
    with pytest.raises(PlanError, match="unknown graph-level mode"):
        SweepPlan(name="x", store="s", targets=[
            TargetSpec("step", ("not_a_mode",), {"arch": "gemma_2b"})
        ]).validate()
    # duplicate (region, mode) pairs across targets are a plan bug
    dup = SweepPlan(name="x", store="s", targets=[
        TargetSpec("pallas", ("fp",), {"kernel": "probe", "sizes": [8]}),
        TargetSpec("pallas", ("fp",), {"kernel": "probe", "sizes": [8]})])
    with pytest.raises(PlanError, match="duplicate"):
        dup.grid()


def test_plan_rejects_bad_family_params_at_build_time(tmp_path):
    """qs on a non-spmxv kernel and unknown spec kwargs must fail when the
    plan is VALIDATED, not later in every worker subprocess at resolve()."""
    with pytest.raises(PlanError, match="spmxv"):
        SweepPlan(name="x", store="s", targets=[
            TargetSpec("pallas", ("fp",),
                       {"kernel": "matmul", "sizes": [128], "qs": [0.5]})
        ]).validate()
    with pytest.raises(PlanError, match="does not accept"):
        SweepPlan(name="x", store="s", targets=[
            TargetSpec("pallas", ("fp",),
                       {"kernel": "matmul", "sizes": [128],
                        "nnz_per_row": 8})
        ]).validate()
    # and the CLI refuses to write the invalid plan file at all
    from repro.fleet.cli import main
    out = str(tmp_path / "bad_plan.json")
    with pytest.raises(SystemExit):
        main(["plan", "--out", out, "--pallas", "matmul", "--sizes", "128",
              "--qs", "0.5", "--store", str(tmp_path / "s.jsonl")])
    assert not os.path.exists(out)


def test_plan_cheap_grid_matches_resolved_pairs(tmp_path):
    """grid() derives names without building targets; it must enumerate
    exactly what pairs() resolves, in the same order."""
    plan, _ = _plan(tmp_path, modes=("fp", "mxu"), sizes=(8, 16))
    assert plan.grid() == [(r.name, m) for r, m in plan.pairs()]


def test_plan_not_a_plan_file(tmp_path):
    path = str(tmp_path / "nope.json")
    with open(path, "w") as f:
        json.dump({"hello": 1}, f)
    with pytest.raises(PlanError, match="not a sweep plan"):
        SweepPlan.load(path)


def test_plan_refuses_compile_once_false(tmp_path):
    """Plans saved before the trace-per-k sweep went carry
    ``"compile_once": true``: it loads (as does a plan without the key)
    and is not written back. False asked for the removed sweep: refused."""
    plan, path = _plan(tmp_path)
    d = plan.to_dict()
    assert "compile_once" not in d
    assert SweepPlan.from_dict({**d, "compile_once": True}).to_dict() == d
    assert SweepPlan.from_dict(d).digest() == plan.digest()
    with pytest.raises(PlanError, match="trace-per-k sweep, which was "
                                        "removed"):
        SweepPlan.from_dict({**d, "compile_once": False})


def _option_strings(parser):
    import argparse

    for action in parser._actions:
        if isinstance(action, argparse._SubParsersAction):
            for sub in action.choices.values():
                yield from _option_strings(sub)
        else:
            yield from action.option_strings


@pytest.mark.parametrize("cli,argv", [
    ("repro.fleet.cli", ["plan", "--out", "p.json", "--pallas", "probe"]),
    ("repro.launch.probe", ["--pallas", "probe"]),
], ids=["fleet", "launch.probe"])
def test_cli_has_no_compile_once_flag(cli, argv, capsys):
    """Neither CLI offers a switch back to trace-per-k sweeps."""
    import importlib

    parser = importlib.import_module(cli).build_parser()
    assert not [o for o in _option_strings(parser) if "compile" in o]
    parser.parse_args(argv)
    with pytest.raises(SystemExit):
        parser.parse_args([*argv, "--no-compile-once"])
    assert "--no-compile-once" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# completeness queries (the per-(region, mode) grid query the executor needs)
# ---------------------------------------------------------------------------

def test_pair_status_lifecycle(tmp_path):
    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    assert st.pair_status("r", "m") == PairStatus(points=0, expected=None,
                                                  done=False)
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 1.0})
    assert st.pair_status("r", "m").points == 1
    assert not st.pair_status("r", "m").complete          # no done marker
    st.append({"kind": "done", "region": "r", "mode": "m", "ks": [0, 2],
               "drift": None, "stopped_early": False, "payload": None})
    ps = st.pair_status("r", "m")
    assert ps.done and ps.expected == 2 and ps.missing == (2,)
    assert not ps.complete                                # truncated store
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 2, "t": 1.0})
    assert st.pair_status("r", "m").complete
    st.close()
    assert st.grid_status([("r", "m"), ("r", "z")])[("r", "z")].points == 0


def test_incomplete_shards_consults_stores_not_bookkeeping(tmp_path,
                                                           synth_measure):
    plan, path = _plan(tmp_path, modes=("fp", "mxu"))
    grid = plan.grid()
    # nothing on disk: every shard owes its slice
    assert _incomplete_shards(plan, grid) == [0, 1]
    # one worker done: only the other still owes
    run_worker(SweepPlan.load(path), index=0, count=2)
    assert _incomplete_shards(plan, grid) == [1]
    run_worker(SweepPlan.load(path), index=1, count=2)
    assert _incomplete_shards(plan, grid) == []


# ---------------------------------------------------------------------------
# the fleet pipeline: spawn -> merge -> classify, resume, crash-heal
# ---------------------------------------------------------------------------

def test_fleet_matches_single_process_and_resumes_free(tmp_path,
                                                       synth_measure,
                                                       capsys):
    """Acceptance: N=2 shards -> merged store -> classification byte-identical
    to the same plan run single-process; a second run --resume launches
    nothing and measures nothing."""
    plan, path = _plan(tmp_path, stem="fan")
    res = run_fleet(path, launcher=in_process_launcher)
    assert res.launched == [0, 1]
    assert res.stats.measured == 0            # classify REPLAYS the merge
    assert {s.status for s in res.state.shards.values()} == {"done"}
    fleet_report = open(plan.report_path(), "rb").read()

    # same targets, fresh store, one process — the reference run
    single, single_path = _plan(tmp_path, stem="single", shards=1)
    reports, stats = run_worker(SweepPlan.load(single_path))
    assert stats.measured > 0
    assert open(single.report_path(), "rb").read() == fleet_report

    # completed fleet: --resume relaunches nothing, replays everything
    res2 = run_fleet(path, resume=True, expect_no_measure=True)
    assert res2.launched == []
    assert res2.stats.measured == 0 and res2.stats.cached > 0
    assert open(plan.report_path(), "rb").read() == fleet_report
    assert report_json(res2.reports) == report_json(res.reports)


def test_fleet_requires_resume_or_fresh_on_existing_state(tmp_path,
                                                          synth_measure):
    _, path = _plan(tmp_path, stem="twice")
    run_fleet(path, launcher=in_process_launcher)
    with pytest.raises(FleetError, match="--resume"):
        run_fleet(path, launcher=in_process_launcher)
    # --fresh restarts from zero: everything is re-measured
    res = run_fleet(path, fresh=True, launcher=in_process_launcher)
    assert res.launched == [0, 1]


def test_fleet_refuses_changed_plan_digest(tmp_path, synth_measure):
    plan, path = _plan(tmp_path, stem="pin")
    run_fleet(path, launcher=in_process_launcher)
    plan.reps = 3                       # a different measurement settings
    plan.save(path)                     # ... under the same plan path
    with pytest.raises(FleetError, match="digest"):
        run_fleet(path, resume=True, launcher=in_process_launcher)


def _kill_after_measuring(store_path):
    """Simulate a shard killed mid-sweep: drop the final 'done' marker and
    tear the (new) trailing point record — exactly the torn-tail shape a
    SIGKILL mid-append leaves behind."""
    lines = open(store_path).read().strip().split("\n")
    assert json.loads(lines[-1])["kind"] == "done"
    with open(store_path, "w") as f:
        f.write("\n".join(lines[:-1]) + "\n")
    with open(store_path, "r+b") as f:
        f.truncate(os.path.getsize(store_path) - 9)


def test_fleet_crash_resume_heals_and_remeasures_only_missing(tmp_path,
                                                              synth_measure):
    """Satellite: a shard killed mid-sweep (truncated trailing line in its
    worker store) is healed by --resume, which re-measures ONLY the missing
    point(s), and the final classification matches the clean run."""
    plan, path = _plan(tmp_path, stem="crash")

    def crashing_launcher(plan_path, p, indices):
        rcs = in_process_launcher(plan_path, p, indices)
        if 0 in indices:
            _kill_after_measuring(p.worker_stores()[0])
            rcs[0] = -9
        return rcs

    with pytest.raises(FleetError, match=r"shard\(s\) \[0\]"):
        run_fleet(path, launcher=crashing_launcher)
    state = FleetState.load(plan.fleet_path())
    assert state.shards[0].status == "failed"
    assert state.shards[1].status == "done"
    # crash aborted pre-merge: the canonical store holds only the pre-launch
    # audit records, no measured points
    canon = CampaignStore(plan.store, readonly=True)
    assert not canon.points
    assert set(canon.audits) == set(plan.grid())
    canon.close()

    res = run_fleet(path, resume=True, launcher=in_process_launcher)
    assert res.launched == [0]                     # ONLY the dead shard
    wstats = json.load(open(plan.worker_stores()[0] + ".stats.json"))
    assert wstats["measured"] == 1                 # the torn point, nothing else
    assert wstats["cached"] > 0                    # the surviving prefix replayed
    assert res.stats.measured == 0

    # reference: same targets, clean single-process run
    single, single_path = _plan(tmp_path, stem="crash_ref", shards=1)
    run_worker(SweepPlan.load(single_path))
    assert open(plan.report_path(), "rb").read() \
        == open(single.report_path(), "rb").read()


def test_fleet_subprocess_end_to_end(tmp_path, synth_measure):
    """The real thing: 2 OS subprocesses (python -m repro.launch.probe
    --plan P --shard i/2), streamed, merged, classified; then a resume that
    spawns nothing."""
    plan, path = _plan(tmp_path, stem="subproc")
    res = run_fleet(path)                          # default: subprocess_launcher
    assert res.launched == [0, 1]
    assert all(s.returncode == 0 for s in res.state.shards.values())
    assert all(s.measured and not s.cached for s in res.state.shards.values())
    assert res.stats.measured == 0
    assert os.path.exists(plan.store)

    res2 = run_fleet(path, resume=True, expect_no_measure=True)
    assert res2.launched == []


# ---------------------------------------------------------------------------
# probe CLI integration (the worker entry + flag conflicts)
# ---------------------------------------------------------------------------

def test_probe_plan_flag_runs_worker_and_replays(tmp_path, synth_measure,
                                                 capsys):
    from repro.launch import probe

    _, path = _plan(tmp_path, stem="cli", modes=("fp",))
    probe.main(["--plan", path, "--shard", "0/2"])
    out = capsys.readouterr().out
    assert "worker store" in out and "points measured" in out
    # whole-plan mode on the merged... here: unsharded store; measures the rest
    probe.main(["--plan", path])
    # an already-complete canonical store replays under --expect-no-measure
    probe.main(["--plan", path, "--expect-no-measure"])
    with pytest.raises(SystemExit, match="conflicting"):
        probe.main(["--plan", path, "--pallas", "probe"])
    with pytest.raises(SystemExit, match="--reps"):
        probe.main(["--plan", path, "--reps", "5"])      # plan owns reps
    with pytest.raises(SystemExit, match="shards"):
        probe.main(["--plan", path, "--shard", "0/3"])   # N != plan.shards


def test_campaign_inspect_against_plan_grid(tmp_path, synth_measure, capsys):
    """``inspect --plan`` checks a store against a plan's FULL expected grid:
    pairs absent from the store entirely are reported (exit 1), a covering
    store passes (exit 0)."""
    from repro.core.campaign import _cli

    plan, path = _plan(tmp_path, stem="inspect", modes=("fp", "mxu"))
    ws = plan.worker_stores()[0]
    run_worker(SweepPlan.load(path), index=0, count=2)   # half the grid
    assert _cli(["inspect", ws, "--plan", path]) == 1
    out = capsys.readouterr().out
    assert "plan 'fleet_probe': 1/2 pair(s) complete" in out
    assert "missing pallas_probe_s8/mxu (absent)" in out

    run_fleet(path, resume=True, launcher=in_process_launcher)
    assert _cli(["inspect", plan.store, "--plan", path]) == 0
    assert "2/2 pair(s) complete" in capsys.readouterr().out


def test_fleet_cli_plan_run_status(tmp_path, synth_measure, capsys):
    from repro.fleet.cli import main

    out_plan = str(tmp_path / "cli_plan.json")
    store = str(tmp_path / "cli" / "store.jsonl")
    assert main(["plan", "--out", out_plan, "--pallas", "probe",
                 "--sizes", "8", "--modes", "fp,mxu", "--reps", "2",
                 "--shards", "2", "--backend", "interpret",
                 "--store", store]) == 0
    assert main(["status", "--plan", out_plan]) == 1      # nothing run yet
    assert main(["run", "--plan", out_plan, "--in-process"]) == 0
    assert main(["run", "--plan", out_plan, "--in-process", "--resume",
                 "--expect-no-measure"]) == 0
    assert main(["status", "--plan", out_plan]) == 0
    out = capsys.readouterr().out
    assert "2/2 pair(s) complete" in out
