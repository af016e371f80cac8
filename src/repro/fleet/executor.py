"""Fleet executor — plan in, classified report out, no hands in between.

``run_fleet`` drives the whole pipeline the ROADMAP called the NEXT step:

  spawn    N worker shards through a pluggable ``Launcher``
           (repro.fleet.launchers: local subprocesses, ssh hosts, or the
           mock fault-injection cluster), each measuring its slice of the
           plan's grid into its own worker store, output streamed
           line-prefixed;
  retry    a ``RetryBudget`` gives failed/incomplete shards more launch
           rounds within one run; completeness is re-derived from the
           stores between rounds, so a retried shard heals its torn store
           and re-measures only missing points, and every attempt lands in
           the ledger (launcher, host, rc, heal stats);
  survive  a killed shard leaves a truncated worker store; resume re-launches
           ONLY the shards whose slice is incomplete, and the campaign layer
           heals the torn tail and re-measures only the missing points;
  merge    worker stores fold into the plan's canonical store
           (``merge_stores`` — idempotent, atomic);
  classify one ``Campaign.characterize`` per region replays the merged store
           (a complete fleet classifies with ZERO new measurements) and the
           cross-region report lands in ``<store>.report.json``.

Ground truth is the stores, not the bookkeeping: shard completeness is
decided by ``CampaignStore.grid_status`` against the plan's grid, so a lying
or lost ``fleet.json`` can never cause double measurement or a hole.
``fleet.json`` (next to the store) records the plan digest, per-shard
status/attempts/attempt-log/stats, the merge manifest, and the final
classification — the fleet's observable state for humans, the ``status``
CLI, and ``fleet_doctor`` (which explains per shard WHY a fleet is
incomplete: missing ks per pair, torn store to be healed, attempts
exhausted).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import socket
import time
from typing import Callable, Optional, Sequence, Union

from repro.fleet.launchers import (FleetError, Launcher, LocalLauncher,  # noqa: F401  (FleetError re-exported)
                                   RetryBudget, ShardOutcome,
                                   resolve_launcher)
from repro.fleet.plan import SweepPlan
from repro.spans import span

log = logging.getLogger("repro.fleet")

FLEET_SCHEMA = 1


# ---------------------------------------------------------------------------
# reporting helpers (shared by the executor, the fleet CLI, and probe)
# ---------------------------------------------------------------------------


def finish_stats(stats, expect_no_measure: bool) -> None:
    """The campaign tail every entry point prints; ``--expect-no-measure``
    turns "the store fully covers this run" into an exit code."""
    print(f"  [{stats.measured} points measured, "
          f"{stats.cached} replayed from store]")
    if expect_no_measure and stats.measured:
        raise SystemExit(
            f"--expect-no-measure: store was incomplete, {stats.measured} "
            "fresh measurements were needed")


def print_report(rep, *, name_line: bool = False) -> None:
    """Human-readable per-mode summary of one RegionReport (one line per
    mode: Abs^raw, fit params, payload verification; then the verdict)."""
    if name_line:
        print(f"  -- {rep.region} (|body|={rep.body_size})")
    for m, r in rep.results.items():
        inj = r.injection
        pay = (f"payload={inj.payload}/{inj.expected} overhead={inj.overhead}"
               if inj else "payload=n/a")
        print(f"  {m:14s} Abs^raw={r.fit.k1:7.1f} t0={r.fit.t0*1e3:8.2f}ms "
              f"slope={r.fit.slope*1e6:9.2f}us/pat {pay}")
    print(f"  => {rep.bottleneck}")


def report_json(reports: dict) -> str:
    """Canonical serialization of {region: RegionReport} — sorted keys and
    regions, so two runs of the same plan produce byte-comparable files."""
    return json.dumps({name: json.loads(rep.to_json())
                       for name, rep in sorted(reports.items())},
                      indent=1, sort_keys=True)


def write_report(path: str, reports: dict) -> str:
    """Atomically write ``report_json(reports)`` to ``path``; returns it."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(report_json(reports) + "\n")
    os.replace(tmp, path)
    return path


def characterize_region(region, modes: Sequence[str], *, controller,
                        store: str, echo_stats: bool = True):
    """Store-backed characterize of ONE region — the spine the benchmark
    harness rides (``benchmarks.common.characterize``)."""
    from repro.core import Campaign

    camp = Campaign(store, controller)
    try:
        rep = camp.characterize(region, list(modes))
    finally:
        camp.store.close()
    if echo_stats and camp.stats.cached:
        print(f"  [{region.name}: {camp.stats.cached} points from store, "
              f"{camp.stats.measured} measured]")
    return rep


# ---------------------------------------------------------------------------
# the static audit gate (repro.analysis) — runs BEFORE any measurement
# ---------------------------------------------------------------------------

AUDIT_CHOICES = ("gate", "warn", "off")

# the runtime measurement-quality gate mirrors the static audit gate, but
# runs AFTER the merge (quality is a property of the measurements, so it
# cannot be checked before they exist): "gate" refuses a fleet whose
# classification was refused (majority-quarantined curves), "warn" reports
# and proceeds, "off" skips evidence attachment entirely
QUALITY_CHOICES = ("gate", "warn", "off")


def _check_audit_choice(audit: str) -> None:
    if audit not in AUDIT_CHOICES:
        raise FleetError(f"audit policy {audit!r}: one of {AUDIT_CHOICES}")


def _check_quality_choice(quality: str) -> None:
    if quality not in QUALITY_CHOICES:
        raise FleetError(
            f"quality policy {quality!r}: one of {QUALITY_CHOICES}")


def _plan_quality(plan: SweepPlan):
    """The plan's declared (QualityPolicy, RemeasureBudget), or (None, None)
    when the plan doesn't opt into the measurement-integrity guard."""
    if plan.quality is None:
        return None, None
    from repro.core import quality_from_dict

    return quality_from_dict(plan.quality)


def _attach_audit_evidence(rep, store):
    """Fold the store's audit records into one RegionReport's classification.

    A no-op for regions without audit records, so a non-audited run
    serializes byte-identically to a pre-audit one."""
    from repro.core import apply_audit_evidence

    audits = {m: rec for (r, m), rec in store.audits.items()
              if r == rep.region and m in rep.results}
    if not audits:
        return rep
    return dataclasses.replace(
        rep, bottleneck=apply_audit_evidence(rep.bottleneck, audits))


def _attach_quality_evidence(rep, store):
    """Fold the store's runtime measurement-quality records into one
    RegionReport's classification (per-mode aggregate of quarantined
    points and why — ``apply_quality_evidence`` decides the downgrade or
    the label refusal).

    A no-op for regions with no quarantined points, so a clean guarded run
    serializes byte-identically to an unguarded one."""
    from repro.core import apply_quality_evidence

    agg = {}
    any_quarantined = False
    for (r, m), per_k in store.quality.items():
        if r != rep.region or m not in rep.results:
            continue
        reasons: dict[str, int] = {}
        quarantined = 0
        for rec in per_k.values():
            if rec.get("verdict") == "quarantine":
                quarantined += 1
                reason = rec.get("reason") or "unknown"
                reasons[reason] = reasons.get(reason, 0) + 1
        agg[m] = {"points": len(per_k), "quarantined": quarantined,
                  "reasons": reasons}
        any_quarantined = any_quarantined or bool(quarantined)
    if not any_quarantined:
        return rep
    return dataclasses.replace(
        rep, bottleneck=apply_quality_evidence(rep.bottleneck, agg))


def _gate_quality(reports: dict, quality: str) -> None:
    """The runtime quality gate: a region whose label was REFUSED by
    ``apply_quality_evidence`` (majority-quarantined curve) fails the fleet
    under ``"gate"``, is printed and tolerated under ``"warn"``."""
    from repro.core.classifier import UNRELIABLE

    if quality == "off":
        return
    bad = {name: rep for name, rep in sorted(reports.items())
           if rep.bottleneck.label == UNRELIABLE}
    if not bad:
        return
    lines = "\n".join(f"  {name}: {rep.bottleneck.explanation}"
                      for name, rep in bad.items())
    msg = (f"quality gate: {len(bad)} region(s) are majority-quarantined — "
           f"the measurements cannot back a label:\n{lines}")
    if quality == "gate":
        raise FleetError(
            msg + "\n`python -m repro.fleet doctor --plan ...` names every "
            "quarantined point and why; re-measure under a quieter clock "
            "with `fleet run --plan ... --resume`, or report anyway with "
            "--quality warn")
    print(f"!! {msg}\n!! --quality warn: reporting anyway")


def audit_fleet_plan(plan: SweepPlan, store=None, *, gate: str = "gate",
                     force: bool = False, echo: bool = True) -> dict:
    """Statically audit every planned (region, mode) pair into the plan's
    canonical store, BEFORE any measurement happens.

    Each pair compiles three static builds (clean / K_LO / K_HI — the clean
    one shared across a region's modes) and the two-point census delta
    decides whether the noise payload survived XLA (``repro.analysis``).
    Verdicts persist as ``audit`` records in the canonical ``CampaignStore``
    — pairs that already carry a record are NOT re-compiled (``force``
    re-audits them; fresh records supersede), so resumed fleets and replay
    runs audit for free.

    ``gate`` policy: ``"gate"`` raises ``FleetError`` when any pair is
    statically DEAD (measuring it would time nothing); ``"warn"`` prints the
    same explanation and proceeds. Callers handle ``"off"`` by not calling
    this at all. A pair whose static build fails is UNAUDITABLE — reported,
    never fatal: a broken build is not proof of a dead payload, and the
    measuring path will surface the real failure.

    Returns ``{(region, mode): audit record}`` for the plan's whole grid.
    """
    from repro.analysis import AuditReport, audit_plan

    owned = store is None
    if owned:
        store = _plan_store(plan, plan.store)
    try:
        grid = plan.grid()
        skip = frozenset() if force else frozenset(store.audits)
        todo = [key for key in grid if key not in skip]
        if todo and echo:
            print(f"== audit: statically verifying {len(todo)} pair(s) "
                  f"({len(grid) - len(todo)} already in store)")
        unauditable: list[tuple] = []
        fresh = audit_plan(plan, skip=skip,
                           on_error=lambda r, m, e:
                               unauditable.append((r, m, e)))
        for rep in fresh:
            store.append({"kind": "audit", **rep.to_dict()})
        records = {key: store.audits[key] for key in grid
                   if key in store.audits}
        if echo:
            for key in grid:
                rec = records.get(key)
                if rec is not None:
                    print("  " + AuditReport.from_dict(rec).explain())
            for r, m, e in unauditable:
                print(f"  {r} × {m}: UNAUDITABLE — {e}")
        dead = [key for key in grid
                if records.get(key, {}).get("verdict") == "dead"]
        if dead:
            lines = "\n".join(
                "  " + AuditReport.from_dict(records[key]).explain()
                for key in dead)
            msg = (f"audit gate: {len(dead)} planned pair(s) carry "
                   "statically DEAD noise — the compiler removed the "
                   f"payload, so measuring them would time nothing:\n{lines}")
            if gate == "gate":
                raise FleetError(
                    msg + "\nfix the noise body (`python -m repro.fleet "
                    "doctor --plan ...` repeats each explanation), or "
                    "measure anyway with --audit warn")
            print(f"!! {msg}\n!! --audit warn: measuring anyway")
        return records
    finally:
        if owned:
            store.close()


# ---------------------------------------------------------------------------
# the single-process worker entry (probe --plan lands here)
# ---------------------------------------------------------------------------


def _plan_store(plan: SweepPlan, path: str, *, readonly: bool = False):
    """Open a store under the plan's declared layout: ``store_format:
    "segments"`` opts writable opens into the segmented backend (readonly
    opens auto-detect — they must never create anything)."""
    from repro.core import CampaignStore

    seg = True if plan.store_format == "segments" else None
    return CampaignStore(path, readonly=readonly,
                         segmented=None if readonly else seg)


def _stats_path(store: str) -> str:
    return store + ".stats.json"


def _write_worker_stats(store: str, stats) -> None:
    with open(_stats_path(store), "w") as f:
        json.dump({"measured": stats.measured, "cached": stats.cached}, f)


def _read_worker_stats(store: str) -> Optional[dict]:
    try:
        with open(_stats_path(store)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _handshake(plan: SweepPlan) -> str:
    """The launcher->worker handshake: a launcher exports the plan digest it
    is driving (``REPRO_FLEET_EXPECT_DIGEST``); a worker whose own plan file
    resolves to a different digest must refuse to measure — an out-of-sync
    plan copy on one host would silently splice a different grid into the
    fleet's stores. Returns the host label to echo in the worker banner."""
    expect = os.environ.get("REPRO_FLEET_EXPECT_DIGEST")
    if expect and expect != plan.digest():
        raise FleetError(
            f"worker handshake failed: the launcher expects plan digest "
            f"{expect} but this worker's plan file resolves to "
            f"{plan.digest()} — the plan copies are out of sync across "
            "hosts; re-distribute the plan file (same bytes => same digest)")
    return os.environ.get("REPRO_FLEET_HOST") or socket.gethostname()


def run_worker(plan: SweepPlan, *, index: Optional[int] = None,
               count: Optional[int] = None, fresh: bool = False,
               expect_no_measure: bool = False,
               header: Optional[str] = None, audit: str = "gate",
               quality: str = "gate"):
    """Execute a plan (or one shard of it) in THIS process.

    ``index``/``count`` given: measure shard ``index`` of ``count``'s slice
    of the plan's pair grid into its worker store and stop — classification
    happens after the merge. Without a shard: run the whole grid into the
    canonical store, classify every region, and write the report file.

    ``audit`` applies to the whole-plan path only (a shard never audits —
    the fleet audits once at the gate): the static noise audit runs before
    any measurement, ``"gate"`` refusing statically-dead pairs, and its
    records back the per-mode evidence attached to every classification.

    A plan that declares a ``quality`` policy measures under the runtime
    integrity guard on BOTH paths (variance gating, sentinels, watchdog —
    quality records land in the store either way); ``quality`` then governs
    the classification side on the whole-plan path: ``"gate"`` refuses a
    majority-quarantined region, ``"warn"`` reports it, ``"off"`` attaches
    no quality evidence.

    Returns ``(results_or_reports, CampaignStats)``.
    """
    from repro.core import Campaign, Controller, remove_store, worker_store
    from repro.core.calibration import resolve_thresholds

    _check_audit_choice(audit)
    _check_quality_choice(quality)

    if index is not None:
        count = plan.shards if count is None else count
        if count != plan.shards:
            raise FleetError(f"--shard I/N count {count} does not match the "
                             f"plan's shards={plan.shards}; the slice "
                             "assignment is part of the plan")
        store = worker_store(plan.store, index, count)
    else:
        store = plan.store
    if fresh:
        remove_store(store)
    host = _handshake(plan)
    title = header or f"fleet plan {plan.name!r} [{plan.digest()}]"
    plan.grid()     # rejects plans whose targets enumerate duplicate pairs
    ctl = Controller(reps=plan.reps)
    qpolicy, qbudget = _plan_quality(plan)
    camp = Campaign(_plan_store(plan, store), ctl, workers=plan.workers,
                    quality=qpolicy, remeasure=qbudget)
    try:
        pairs = plan.pairs()
        if index is not None:
            print(f"== {title} [shard {index}/{count}] ({len(pairs)}-pair "
                  f"grid; worker store: {store})")
            print(f"  [worker handshake: plan {plan.digest()}, host {host}, "
                  f"pid {os.getpid()}]")
            res = camp.measure_pairs(pairs, index=index, count=count)
            for (r, m), mr in sorted(res.items()):
                print(f"  {r}/{m}: Abs^raw={mr.fit.k1:7.1f} "
                      f"t0={mr.fit.t0*1e3:8.2f}ms")
            if not res:
                print(f"  (no pairs land on shard {index} of {count})")
            print("  [classification happens after the merge; a shard sees "
                  "only its slice]")
            _write_worker_stats(store, camp.stats)
            finish_stats(camp.stats, expect_no_measure)
            return res, camp.stats

        print(f"== {title} (campaign store: {store})")
        if audit != "off":
            audit_fleet_plan(plan, camp.store, gate=audit)
        low, high, prov = resolve_thresholds(camp.store)
        camp.thresholds = (low, high)
        if prov != "default":
            print(f"  [classification thresholds: {prov} "
                  f"low={low:g} high={high:g}]")
        reports = {}
        many = sum(len(regions) for _, regions in plan.resolve()) > 1
        for spec, regions in plan.resolve():
            for region in regions:
                rep = _attach_audit_evidence(
                    camp.characterize(region, list(spec.modes)), camp.store)
                if quality != "off":
                    rep = _attach_quality_evidence(rep, camp.store)
                reports[region.name] = rep
                print_report(rep, name_line=many)
        _gate_quality(reports, quality)
        write_report(plan.report_path(), reports)
        finish_stats(camp.stats, expect_no_measure)
        return reports, camp.stats
    finally:
        camp.store.close()


# ---------------------------------------------------------------------------
# fleet state (fleet.json)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardState:
    """One shard's ledger entry in ``fleet.json``.

    ``attempts`` counts LIFETIME launches (across resumes — what
    ``RetryBudget.per_shard_cap`` is checked against) and ``attempt_log``
    records each one: {attempt, launcher, host, rc, measured, cached} —
    ``measured``/``cached`` are the worker's heal stats (a retry that
    replayed N cached points and measured only the missing ones shows
    exactly that). Status vocabulary: pending | running | done | failed |
    exhausted (per-shard attempt cap reached)."""
    index: int
    store: str
    status: str = "pending"
    returncode: Optional[int] = None
    attempts: int = 0
    measured: Optional[int] = None
    cached: Optional[int] = None
    host: Optional[str] = None
    attempt_log: list = dataclasses.field(default_factory=list)


class FleetState:
    """The durable fleet ledger. Advisory (stores are ground truth), but it
    is what ``status`` shows and what resume uses to report history."""

    def __init__(self, path: str, plan_digest: str,
                 shard_stores: Sequence[str]):
        self.path = path
        self.plan_digest = plan_digest
        self.shards = {i: ShardState(i, s)
                       for i, s in enumerate(shard_stores)}
        self.merge: Optional[dict] = None
        self.classification: Optional[dict] = None
        self.stats: Optional[dict] = None

    def to_dict(self) -> dict:
        """The JSON form written to ``fleet.json`` (schema-versioned)."""
        return {"fleet": FLEET_SCHEMA, "plan": self.plan_digest,
                "shards": {str(i): dataclasses.asdict(s)
                           for i, s in self.shards.items()},
                "merge": self.merge, "classification": self.classification,
                "stats": self.stats}

    def save(self) -> None:
        """Atomically rewrite ``fleet.json`` with the current state."""
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)

    @classmethod
    def load(cls, path: str) -> "FleetState":
        """Load a ``fleet.json`` (older files without host/attempt_log
        fields load with defaults)."""
        with open(path) as f:
            d = json.load(f)
        if d.get("fleet") != FLEET_SCHEMA:
            raise FleetError(f"{path}: not a fleet state file "
                             f"(fleet={d.get('fleet')!r})")
        state = cls(path, d.get("plan", ""), [])
        state.shards = {int(i): ShardState(**s)
                        for i, s in d.get("shards", {}).items()}
        state.merge = d.get("merge")
        state.classification = d.get("classification")
        state.stats = d.get("stats")
        return state


# ---------------------------------------------------------------------------
# shard launchers (implementations live in repro.fleet.launchers)
# ---------------------------------------------------------------------------


def subprocess_launcher(plan_path: str, plan: SweepPlan,
                        indices: Sequence[int]) -> dict[int, int]:
    """Back-compat shim for the pre-Launcher API: a subprocess
    ``LocalLauncher`` round, returned as the legacy {index: returncode}."""
    out = LocalLauncher().launch(plan_path, plan, indices)
    return {i: o.rc for i, o in out.items()}


def in_process_launcher(plan_path: str, plan: SweepPlan,
                        indices: Sequence[int]) -> dict[int, int]:
    """Back-compat shim for the pre-Launcher API: an in-process
    ``LocalLauncher`` round, returned as the legacy {index: returncode}."""
    out = LocalLauncher(in_process=True).launch(plan_path, plan, indices)
    return {i: o.rc for i, o in out.items()}


class _CallableLauncher(Launcher):
    """Adapter for legacy ``fn(plan_path, plan, indices) -> {i: rc}``
    launcher callables (still accepted by ``run_fleet(launcher=...)``)."""

    def __init__(self, fn: Callable):
        self.fn = fn
        self.name = getattr(fn, "__name__", "callable")

    def launch(self, plan_path, plan, indices, *, attempts=None):
        """Call the wrapped function and lift rcs into ShardOutcomes."""
        return {i: ShardOutcome(int(rc), None)
                for i, rc in self.fn(plan_path, plan, indices).items()}


def _as_launcher(launcher: Union[Launcher, Callable, None],
                 plan: SweepPlan) -> Launcher:
    """Normalize run_fleet's ``launcher`` argument: None -> resolve from the
    plan's declarative spec (default local subprocesses); a ``Launcher`` is
    used as-is; any other callable goes through the legacy adapter."""
    if launcher is None:
        return resolve_launcher(plan=plan)
    if isinstance(launcher, Launcher):
        return launcher
    return _CallableLauncher(launcher)


# ---------------------------------------------------------------------------
# the fleet pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetResult:
    """What ``run_fleet`` hands back: the plan, one RegionReport per region,
    the finalize replay's CampaignStats, the saved FleetState ledger, and
    the shard indices that were (re)launched during this run."""
    plan: SweepPlan
    reports: dict
    stats: object                    # CampaignStats of the finalize replay
    state: FleetState
    launched: list[int]              # shard indices (re)launched this run


def _incomplete_shards(plan: SweepPlan, grid, *,
                       heal: bool = False) -> list[int]:
    """Which shards still owe measurements — decided from the stores alone.

    The canonical store is consulted first: once a fleet has merged (or the
    same plan ran single-process), a complete canonical store means NO shard
    has anything left to do, even if worker stores were deleted.

    ``heal``: treat a complete pair that carries QUARANTINED points as still
    owing, so a resume re-launches its shard and the worker re-measures the
    condemned points (hopefully under a quieter clock)."""
    from repro.core import CampaignStore, store_exists

    def ok(ps) -> bool:
        return ps.complete and not (heal and ps.quarantined)

    if store_exists(plan.store):
        st = CampaignStore(plan.store, readonly=True)
        if all(ok(ps) for ps in st.grid_status(grid).values()):
            return []
    out = []
    for i in range(plan.shards):
        mine = grid[i::plan.shards]
        if not mine:
            continue
        ws = plan.worker_stores()[i]
        if not store_exists(ws):
            out.append(i)
            continue
        # readonly: completeness probing must not heal anything — the worker
        # owns its store and heals the torn tail itself on relaunch
        st = CampaignStore(ws, readonly=True)
        if not all(ok(ps) for ps in st.grid_status(mine).values()):
            out.append(i)
    return out


def _classify(plan: SweepPlan, quality: str = "gate"):
    """Merge-side finalize: replay the canonical store into one RegionReport
    per region (a complete store measures nothing here — quarantined points
    are NOT healed by finalize; it must classify what the fleet measured,
    with the quality evidence attached when ``quality`` != "off"). A
    ``calib`` record in the store (``repro.core.calibration``) swaps the
    classifier's paper-default thresholds for the fitted ones."""
    from repro.core import Campaign, Controller
    from repro.core.calibration import resolve_thresholds

    qpolicy, qbudget = _plan_quality(plan)
    ctl = Controller(reps=plan.reps)
    camp = Campaign(_plan_store(plan, plan.store), ctl, workers=plan.workers,
                    quality=qpolicy, remeasure=qbudget,
                    heal_quarantined=False)
    low, high, _prov = resolve_thresholds(camp.store)
    camp.thresholds = (low, high)
    try:
        reports = {}
        for spec, regions in plan.resolve():
            for region in regions:
                rep = _attach_audit_evidence(
                    camp.characterize(region, list(spec.modes)), camp.store)
                if quality != "off":
                    rep = _attach_quality_evidence(rep, camp.store)
                reports[region.name] = rep
    finally:
        camp.store.close()
    return reports, camp.stats


def _clean_fleet(plan: SweepPlan) -> None:
    from repro.core import remove_store

    stores = [plan.store] + plan.worker_stores()
    for s in stores:
        remove_store(s)            # removes either layout (file/segment dir)
    paths = [plan.fleet_path(), plan.report_path()]
    paths += [_stats_path(ws) for ws in plan.worker_stores()]
    for p in paths:
        if os.path.exists(p):
            os.unlink(p)


def run_fleet(plan_path: str, *, resume: bool = False, fresh: bool = False,
              expect_no_measure: bool = False,
              launcher: Union[Launcher, Callable, None] = None,
              retry: Optional[RetryBudget] = None,
              audit: str = "gate", quality: str = "gate") -> FleetResult:
    """Plan → audit → spawn (with retries) → merge → classify, resumably.

    * the static noise audit runs FIRST, before anything launches: every
      planned pair is verified against the compiler (``audit_fleet_plan``);
      under the default ``audit="gate"`` a statically-dead pair refuses the
      whole fleet (no machine time is spent measuring nothing), ``"warn"``
      proceeds anyway, ``"off"`` skips the audit. Audit records live in the
      canonical store, so resumes never re-compile them, and the classify
      step attaches them as per-mode evidence;
    * first run: launches every shard whose slice is incomplete (all of
      them), merges, classifies;
    * within one call, the ``retry`` budget (or the plan's declarative
      ``retry`` settings) governs how many launch rounds failed/incomplete
      shards get — completeness is re-derived from the STORES after every
      round, so a retried shard heals its torn store and re-measures only
      missing points; every attempt lands in ``fleet.json``'s per-shard
      attempt log (launcher, host, rc, heal stats);
    * a plan that declares a ``quality`` policy measures every point under
      the runtime integrity guard; after the merge, ``quality="gate"``
      refuses a fleet whose classification was refused (majority-quarantined
      curve — the ``unreliable`` label), ``"warn"`` reports it and writes
      the report anyway, ``"off"`` attaches no quality evidence;
    * ``resume`` after a crash: re-launches ONLY incomplete shards, then
      merges and classifies as usual; a resume also re-launches shards whose
      pairs are complete but QUARANTINED, so the workers re-measure the
      condemned points (run it under a quieter clock to heal the fleet);
    * ``resume`` on a completed fleet: launches nothing and the classify
      step replays the canonical store with ZERO new measurements;
    * ``fresh``: delete every store/state file of this plan first.

    ``launcher`` is a ``Launcher`` (Local/SSH/MockCluster), a legacy
    ``fn(plan_path, plan, indices) -> {i: rc}`` callable, or None to resolve
    from the plan's ``launcher`` spec (default: local subprocesses).

    Raises ``FleetError`` when fleet state exists for a different plan
    digest, when state exists and neither flag was given, when shards still
    owe measurements after the last allowed attempt round, or when a shard
    has exhausted its lifetime ``per_shard_cap``.

    What the plan's targets hold on the device is freed when the campaign
    has reported (``SweepPlan.release``), so campaigns run back to back do
    not hold two model engines at once.
    """
    _check_audit_choice(audit)
    _check_quality_choice(quality)
    plan = SweepPlan.load(plan_path)
    if fresh:
        _clean_fleet(plan)
    fleet_path = plan.fleet_path()
    state = None
    if os.path.exists(fleet_path):
        state = FleetState.load(fleet_path)
        if state.plan_digest != plan.digest():
            raise FleetError(
                f"{fleet_path} belongs to plan digest {state.plan_digest}, "
                f"this plan is {plan.digest()}; a changed plan must not "
                "splice into old shards — use --fresh to restart")
        if not resume:
            raise FleetError(
                f"{fleet_path} already exists; use --resume to continue (or "
                "replay) this fleet, or --fresh to restart it")
    grid = plan.grid()
    if state is None:
        state = FleetState(fleet_path, plan.digest(), plan.worker_stores())
    budget = retry if retry is not None \
        else RetryBudget.from_dict(plan.retry)
    lch = _as_launcher(launcher, plan)
    if audit != "off":
        # fail-fast: a statically-dead pair refuses the fleet BEFORE any
        # shard launches; records land in the canonical store (pre-merge,
        # so the merge streams them through) and back the evidence below
        with span("campaign.fleet.audit", pairs=len(grid)):
            audit_fleet_plan(plan, gate=audit)

    incomplete = sorted(_incomplete_shards(plan, grid, heal=resume))
    for i, ss in state.shards.items():
        ss.status = "pending" if i in incomplete else "done"
    state.save()

    launched: list[int] = []
    round_no = 0
    while incomplete:
        capped = [i for i in incomplete
                  if budget.per_shard_cap
                  and state.shards[i].attempts >= budget.per_shard_cap]
        for i in capped:
            state.shards[i].status = "exhausted"
        runnable = [i for i in incomplete if i not in capped]
        if not runnable:
            state.save()
            raise FleetError(
                f"shard(s) {sorted(capped)} exhausted the lifetime "
                f"per-shard attempt cap ({budget.per_shard_cap}); "
                "fleet.json records every attempt (launcher, host, rc) — "
                "`python -m repro.fleet doctor` explains each shard; fix "
                "the cause, then raise --per-shard-cap or restart with "
                "--fresh")
        if round_no >= budget.max_attempts:
            break
        round_no += 1
        delay = budget.delay(round_no)
        if delay:
            print(f"== retry backoff: sleeping {delay:.1f}s before attempt "
                  f"round {round_no}/{budget.max_attempts}")
            time.sleep(delay)
        print(f"== fleet {plan.name!r} [{plan.digest()}]: "
              f"{len(grid)}-pair grid, launching shard(s) {runnable} of "
              f"{plan.shards} (round {round_no}/{budget.max_attempts}, "
              f"launcher {lch.name})")
        attempts_map = {}
        for i in runnable:
            ss = state.shards[i]
            ss.status = "running"
            ss.attempts += 1
            attempts_map[i] = ss.attempts
            # a stale stats file from a previous attempt must not be
            # misattributed to this one (a worker that never runs writes
            # no stats; the ledger then honestly records None)
            try:
                os.unlink(_stats_path(ss.store))
            except OSError:
                pass
        state.save()
        with span("campaign.fleet.launch", round=round_no,
                  shards=len(runnable)):
            outcomes = lch.launch(plan_path, plan, runnable,
                                  attempts=attempts_map)
        still = set(_incomplete_shards(plan, grid, heal=resume))
        for i in runnable:
            ss = state.shards[i]
            o = outcomes.get(i)
            ss.returncode = None if o is None else o.rc
            ss.host = None if o is None else o.host
            ss.status = "failed" if i in still else "done"
            wstats = _read_worker_stats(ss.store)
            if wstats:
                ss.measured = wstats.get("measured")
                ss.cached = wstats.get("cached")
            ss.attempt_log.append({
                "attempt": ss.attempts, "launcher": lch.name,
                "host": ss.host, "rc": ss.returncode,
                "measured": (wstats or {}).get("measured"),
                "cached": (wstats or {}).get("cached")})
            if i not in launched:
                launched.append(i)
        state.save()
        incomplete = sorted(still)
    if incomplete:
        codes = {i: state.shards[i].returncode for i in incomplete}
        raise FleetError(
            f"shard(s) {sorted(incomplete)} did not complete after "
            f"{round_no} attempt round(s) (returncodes {codes}); completed "
            "work is preserved in the worker stores — `python -m repro.fleet "
            "doctor` explains each shard, and re-running with --resume (or "
            "a higher --max-attempts) heals and finishes them")
    if not launched:
        print(f"== fleet {plan.name!r} [{plan.digest()}]: all "
              f"{plan.shards} shard slice(s) already complete, "
              "nothing to launch")

    from repro.core import merge_stores, store_exists

    sources = [ws for ws in plan.worker_stores() if store_exists(ws)]
    if sources:
        # the canonical store (when present) streams FIRST so freshly
        # re-measured worker records supersede any stale merged ones (an
        # incremental merge into a segmented canonical store skips the
        # self-source and adopts only never-seen worker segments)
        if store_exists(plan.store):
            sources = [plan.store] + sources
        with span("campaign.fleet.merge", sources=len(sources)) as sp:
            mstats = merge_stores(plan.store, sources)
            sp.set_metadata(records_in=mstats.records_in,
                            records_out=mstats.records_out)
        state.merge = {"dest": plan.store, "sources": sources,
                       "records_in": mstats.records_in,
                       "records_out": mstats.records_out,
                       "conflicts": sorted(set(map(tuple, mstats.conflicts)))}
        state.merge["conflicts"] = [list(c) for c in
                                    state.merge["conflicts"]]
        if mstats.incremental:
            state.merge["segments_new"] = mstats.segments_new
            state.merge["segments_skipped"] = mstats.segments_skipped
        print(f"== merge: {mstats}")

    with span("campaign.fleet.classify",
              regions=len({r for r, _ in grid})):
        reports, cstats = _classify(plan, quality)
    state.classification = {
        name: {"label": rep.bottleneck.label,
               "confidence": rep.bottleneck.confidence,
               "abs": rep.absorptions()}
        for name, rep in sorted(reports.items())}
    state.stats = {"measured": cstats.measured, "cached": cstats.cached}
    state.save()
    # the ledger records the refused classification (forensics) but the gate
    # refuses to WRITE a report a majority-quarantined fleet cannot back
    _gate_quality(reports, quality)
    with span("campaign.fleet.report"):
        write_report(plan.report_path(), reports)
        print(f"== classification ({plan.report_path()}):")
        for name, rep in sorted(reports.items()):
            print(f"  {name}: {rep.bottleneck}")
        finish_stats(cstats, expect_no_measure)
    plan.release()
    return FleetResult(plan=plan, reports=reports, stats=cstats, state=state,
                       launched=launched)


# ---------------------------------------------------------------------------
# fleet doctor — explain, per shard, why the fleet is (in)complete
# ---------------------------------------------------------------------------


def _pair_lines(store_path: str, mine, canon_status) -> tuple[list[str], int]:
    """Diagnose one shard's slice against its worker store (and the
    canonical store): returns (report lines, #pairs still owing)."""
    from repro.core import (CampaignStore, CampaignStoreError, is_segmented,
                            manifest_status, store_exists)
    from repro.core.campaign import read_store_records

    lines: list[str] = []
    wstore = None
    if not store_exists(store_path):
        status = {}
        lines.append(f"  worker store {store_path}: absent")
    else:
        try:
            if is_segmented(store_path):
                ms = manifest_status(store_path)
                if ms["orphans"]:
                    lines.append(
                        f"  worker store {store_path}: {ms['orphans']} "
                        f"unsealed segment(s) ({ms['orphan_bytes']} byte(s))"
                        " — a live or killed writer; healed (sealed, torn "
                        "tail truncated) on the next writable open")
            else:
                records, valid = read_store_records(store_path)
                size = os.path.getsize(store_path)
                if valid < size:
                    lines.append(
                        f"  worker store {store_path}: torn tail — "
                        f"{size - valid} byte(s) past the last valid record "
                        "(a SIGKILL mid-append; healed automatically on the "
                        "next load, costing at most one point)")
            wstore = CampaignStore(store_path, readonly=True)
            status = wstore.grid_status(mine)
        except CampaignStoreError as e:
            lines.append(f"  worker store {store_path}: CORRUPT beyond the "
                         f"final record — {e}; delete it and relaunch the "
                         "shard (--resume re-measures its whole slice)")
            status = {}
    owing = 0
    for pair in mine:
        r, m = pair
        # quarantine evidence lives in the worker store even before any
        # merge, so a hung-kernel timeout is explainable right after the
        # failed round, not only once a canonical store exists
        qwhy = ""
        if wstore is not None:
            per_k = wstore.quality.get(pair, {})
            by: dict[str, list[int]] = {}
            for k in wstore.quarantined_ks(r, m):
                reason = per_k.get(k, {}).get("reason") or "unknown"
                by.setdefault(reason, []).append(k)
            qwhy = "; ".join(f"{reason} at k(s) {sorted(ks)}"
                             for reason, ks in sorted(by.items()))
        if canon_status and canon_status.get(pair) \
                and canon_status[pair].complete:
            continue                      # already satisfied by the merge
        ps = status.get(pair)
        if ps is None or (not ps.done and not ps.points):
            owing += 1
            lines.append(f"  {r}/{m}: absent — never measured")
            if qwhy:      # e.g. the sensitivity probe itself timed out
                lines.append(f"    quarantined: {qwhy}")
        elif ps.complete:
            if qwhy:
                lines.append(
                    f"  {r}/{m}: complete but quarantined — {qwhy}; "
                    "`--resume` re-measures exactly these points")
            continue
        elif ps.done and ps.missing:
            owing += 1
            lines.append(
                f"  {r}/{m}: done-marked but {ps.points}/{ps.expected} "
                f"point(s) present — missing k(s) {sorted(ps.missing)}; a "
                "relaunch re-measures ONLY these")
            if qwhy:
                lines.append(f"    quarantined: {qwhy}")
        else:
            owing += 1
            lines.append(
                f"  {r}/{m}: in progress — {ps.points} point(s), no done "
                "marker (the k grid is adaptive; a relaunch resumes at the "
                "first missing k)")
            if qwhy:
                lines.append(f"    quarantined: {qwhy}")
    return lines, owing


def fleet_doctor(plan: SweepPlan, budget: Optional[RetryBudget] = None,
                 *, explain: bool = False) -> tuple[int, str]:
    """Explain, per shard, why a fleet is incomplete — the forensics behind
    ``_incomplete_shards``'s yes/no answer.

    For every shard: its ledger history (attempts, launcher, host, rc, heal
    stats from ``fleet.json``), whether its lifetime attempt cap is
    exhausted, the worker store's physical condition (torn tail to be
    healed, corruption), and each owing (region, mode) pair with its
    missing ks when the ``done`` marker pins them. Returns
    ``(exit_code, report)``: 0 when the grid is fully covered, 1 otherwise.

    ``explain`` appends the classification forensics for a COVERED grid: a
    measurement-free replay of every region's classification, rendering
    the strategy tree's evaluated decision path — which node fired, under
    which thresholds, whether those were calibrated or the paper defaults,
    and any audit/quality downgrades.
    """
    from repro.core import CampaignStore, store_exists

    grid = plan.grid()
    budget = budget if budget is not None else RetryBudget.from_dict(plan.retry)
    state = None
    if os.path.exists(plan.fleet_path()):
        state = FleetState.load(plan.fleet_path())
    out = [f"== fleet doctor: plan {plan.name!r} [{plan.digest()}] — "
           f"{len(grid)} pair(s) over {plan.shards} shard(s)"]
    if state is None:
        out.append(f"fleet ledger {plan.fleet_path()}: not created yet "
                   "(no run attempted)")
    elif state.plan_digest != plan.digest():
        out.append(f"fleet ledger {plan.fleet_path()}: STALE — built by "
                   f"plan digest {state.plan_digest}; --fresh required")
    canon_status = None
    if store_exists(plan.store):
        canon = CampaignStore(plan.store, readonly=True)
        canon_status = canon.grid_status(grid)
        done = sum(ps.complete for ps in canon_status.values())
        out.append(f"canonical store {plan.store}: {done}/{len(grid)} "
                   "pair(s) complete")
        audited = {key: canon.audits[key] for key in grid
                   if key in canon.audits}
        if audited:
            from repro.analysis import AuditReport

            n_dead = sum(r.get("verdict") == "dead"
                         for r in audited.values())
            n_intact = sum(r.get("verdict") == "intact"
                           for r in audited.values())
            out.append(f"static audit: {len(audited)}/{len(grid)} pair(s) "
                       f"audited — {n_intact} intact, {n_dead} dead")
            for key in grid:
                rec = audited.get(key)
                if rec is not None and rec.get("verdict") != "intact":
                    out.append("  " + AuditReport.from_dict(rec).explain())
                    if rec.get("verdict") == "dead":
                        out.append("    (the audit gate refuses this pair; "
                                   "fix the noise body or run with "
                                   "--audit warn)")
        # runtime measurement quality: quarantined points, and why
        qpairs = {key: canon.quarantined_ks(*key) for key in grid}
        qpairs = {key: ks for key, ks in qpairs.items() if ks}
        if qpairs:
            nq = sum(len(ks) for ks in qpairs.values())
            out.append(f"measurement quality: {nq} quarantined point(s) "
                       f"across {len(qpairs)} pair(s)")
            for (r, m), ks in sorted(qpairs.items()):
                per_k = canon.quality.get((r, m), {})
                reasons: dict[str, list[int]] = {}
                for k in ks:
                    reason = per_k.get(k, {}).get("reason") or "unknown"
                    reasons.setdefault(reason, []).append(k)
                why = "; ".join(f"{reason} at k(s) {sorted(kk)}"
                                for reason, kk in sorted(reasons.items()))
                out.append(f"  {r}/{m}: {why}")
                for k in ks:
                    detail = per_k.get(k, {}).get("detail")
                    if detail:
                        out.append(f"    k={k}: {detail}")
            out.append("  (a quarantined point condemns its reading, not "
                       "the pair; `fleet run --plan ... --resume` "
                       "re-measures exactly these points — run it under a "
                       "quieter clock)")
        # implausible baseline drift the campaign refused to correct for
        for key in grid:
            rec = canon.done.get(key)
            drift = (rec or {}).get("drift")
            if drift is not None and not (0.5 < drift < 2.0):
                r, m = key
                out.append(f"  {r}/{m}: implausible baseline drift factor "
                           f"{drift:.3g} recorded — outside (0.5, 2.0), so "
                           "drift correction was refused and the sweep's "
                           "tail is suspect; re-measure under a steadier "
                           "clock")
    else:
        out.append(f"canonical store {plan.store}: absent (no merge yet)")
    total_owing = 0
    for i in range(plan.shards):
        mine = grid[i::plan.shards]
        ss = state.shards.get(i) if state else None
        hist = ""
        if ss is not None and ss.attempt_log:
            tries = ", ".join(
                f"#{a.get('attempt')}: {a.get('launcher')}"
                + (f"@{a.get('host')}" if a.get("host") else "")
                + f" rc={a.get('rc')}"
                + (f" measured={a.get('measured')} cached={a.get('cached')}"
                   if a.get("measured") is not None else "")
                for a in ss.attempt_log)
            hist = f" — attempts: [{tries}]"
        elif ss is not None and ss.attempts:
            hist = f" — {ss.attempts} attempt(s), rc={ss.returncode}"
        if not mine:
            out.append(f"shard {i}: no pairs land on this shard{hist}")
            continue
        lines, owing = _pair_lines(plan.worker_stores()[i], mine,
                                   canon_status)
        total_owing += owing
        verdict = "complete" if not owing else f"INCOMPLETE ({owing} " \
            f"pair(s) owing)"
        out.append(f"shard {i}: {verdict}{hist}")
        if owing:
            if ss is not None and budget.per_shard_cap \
                    and ss.attempts >= budget.per_shard_cap:
                out.append(
                    f"  attempts exhausted: lifetime per-shard cap "
                    f"{budget.per_shard_cap} reached ({ss.attempts} used) — "
                    "raise --per-shard-cap, or --fresh to restart")
            out.extend(lines)
    if total_owing:
        out.append(f"== verdict: INCOMPLETE — {total_owing} pair(s) still "
                   "owe measurements; `python -m repro.fleet run --plan ... "
                   "--resume` re-launches only the owing shards")
    else:
        out.append("== verdict: COMPLETE — every pair is covered; a resume "
                   "replays with zero new measurements")
    if explain:
        out.extend(_explain_lines(plan, covered=not total_owing))
    return (1 if total_owing else 0), "\n".join(out)


def _explain_lines(plan: SweepPlan, *, covered: bool) -> list[str]:
    """The ``doctor --explain`` section: replay the covered store's
    classification (readonly, measurement-free) and render each region's
    evaluated decision path."""
    from repro.core import Campaign, CampaignStore, Controller, store_exists
    from repro.core.calibration import resolve_thresholds

    out = ["== explain: decision path per region"]
    if not store_exists(plan.store):
        out.append("  canonical store absent — run the fleet (or merge the "
                   "worker stores) first")
        return out
    if not covered:
        out.append("  grid incomplete — explain replays the store without "
                   "measuring, so it needs full coverage first")
        return out
    store = CampaignStore(plan.store, readonly=True)
    low, high, prov = resolve_thresholds(store)
    out.append(f"  thresholds: {prov} (low={low:g}, high={high:g})")
    qpolicy, qbudget = _plan_quality(plan)
    ctl = Controller(reps=plan.reps)
    camp = Campaign(store, ctl, workers=plan.workers, quality=qpolicy,
                    remeasure=qbudget, heal_quarantined=False,
                    thresholds=(low, high))
    reports = {}
    try:
        for spec, regions in plan.resolve():
            for region in regions:
                rep = _attach_audit_evidence(
                    camp.characterize(region, list(spec.modes)), store)
                reports[region.name] = _attach_quality_evidence(rep, store)
    except Exception as e:                  # noqa: BLE001 — forensics only
        out.append(f"  explain failed to replay the store: {e}")
        return out
    for name, rep in sorted(reports.items()):
        b = rep.bottleneck
        out.append(f"  {name}: {b.label} (confidence {b.confidence:.2f})")
        out.append("    absorptions: " + ", ".join(
            f"{m}={r.fit.k1:.1f}" for m, r in sorted(rep.results.items())))
        path = b.path or {}
        nodes = path.get("nodes", [])
        if nodes:
            chain = " -> ".join(f"{n['node']}{'*' if n['fired'] else ''}"
                                for n in nodes)
            out.append(f"    path [{path.get('strategy')}]: {chain} "
                       "(* = fired)")
        out.append(f"    why: {b.explanation}")
        if b.evidence is not None:
            bad = [e["mode"] for e in b.evidence if not e["supports"]]
            if bad:
                out.append("    audit downgrade: conflicting mode(s) "
                           + ", ".join(sorted(bad)))
        if b.quality is not None:
            quar = [q["mode"] for q in b.quality if q["quarantined"]]
            if quar:
                out.append("    quality downgrade: quarantined point(s) in "
                           + ", ".join(sorted(quar)))
    return out
