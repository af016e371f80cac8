"""Noise-injection bottleneck probe — the paper's tool applied to this
framework's own train/serve steps and to the Pallas kernel layer, and the
FLEET's single-process worker entry.

Every measured path runs through the fleet plan/executor spine
(``repro.fleet``): the CLI flags build a one-target ``SweepPlan`` and hand it
to ``run_worker`` — the same code path a fleet shard executes — so ad-hoc
probes, subprocess shards, and declarative plan files all measure through
one campaign tail (store naming, shard dispatch, reporting).

Measured mode (default; reduced config, host backend) runs as a resumable
CAMPAIGN: every (mode, k, t) point persists to a JSONL store under
``experiments/campaigns/`` and re-running skips everything already measured.
The sweep delivers k at run time (one runtime-k executable per mode instead
of one per sweep point):

    PYTHONPATH=src python -m repro.launch.probe --arch gemma-2b --smoke \
        --kind train --modes fp_add32,vmem_ld,hbm_stream \
        [--store PATH] [--fresh] [--workers N]

Serve mode probes the paged serving engine as TWO regions — the batched
prefill and the decode tick — under one campaign, so the two phases of one
workload classify separately (docs/methodology.md §Serving):

    PYTHONPATH=src python -m repro.launch.probe --serve --arch gemma-2b \
        --seq 16 --batch 4 [--modes fp_add32,hbm_stream] [--store PATH]

Pallas mode probes one of the real kernels (matmul / spmxv / attention /
probe) through the SAME campaign machinery — the noise quantity is a
runtime operand of the kernel itself, so the whole sweep and its payload
check compile one Pallas executable per mode. It runs on the TPU;
``--backend interpret`` runs the kernels in the Pallas interpreter instead
(tests, docs):

    PYTHONPATH=src python -m repro.launch.probe --pallas spmxv \
        [--backend interpret] [--modes fp,vmem] [--store PATH] \
        [--expect-no-measure]

Fleet worker mode executes a slice of a saved ``SweepPlan`` — this is what
``python -m repro.fleet run`` spawns (through any of its launchers: local
subprocesses, ssh hosts, the mock cluster) and the per-host command of the
manual multi-host recipe (docs/orchestration.md). Launchers hand the worker
a handshake env: ``REPRO_FLEET_EXPECT_DIGEST`` (the worker refuses to run
if its plan file's digest disagrees — an out-of-sync plan copy on one host
must not splice a different grid into the fleet) and ``REPRO_FLEET_HOST``
(echoed in the worker banner and the fleet ledger's attempt log):

    PYTHONPATH=src python -m repro.launch.probe --plan plan.json --shard 0/2
    PYTHONPATH=src python -m repro.launch.probe --plan plan.json \
        --expect-no-measure        # whole plan in-process; replay check

Legacy ad-hoc fan-out still works: ``--shard I/N`` without ``--plan``
measures a disjoint slice of the flag-built grid into a per-worker store;
merge afterwards with ``python -m repro.core.campaign merge`` (or just run
the same grid as a plan through ``repro.fleet``, which merges for you).

``--expect-no-measure`` turns "the store fully covers this probe" into an
exit code, so scripts and CI can assert the round-trip measured nothing.

Every measured path classifies under the store's calibrated thresholds when
a ``calib`` record is present (``python -m repro.fleet calibrate run`` fits
one; ``... calibrate apply --to STORE`` copies it into a probe's store) and
falls back to the paper defaults otherwise — the worker banner prints the
threshold provenance whenever it is not the default.

Analytic mode (full config, TPU v5e target, reads the dry-run artifact) runs
through the SAME campaign machinery — predictions persist as ``pred``
records (curve + fit + HardwareConfig/terms/settings) and replay on re-run:

    PYTHONPATH=src python -m repro.launch.probe --arch gemma-2b \
        --shape train_4k --analytic [--dryrun-dir experiments/dryrun/16x16] \
        [--store PATH] [--fresh]

All paths report Abs^raw per mode + the bottleneck classification; measured
modes also verify the payload statically (surviving noise ops in optimized
HLO, or the exact nacc oracle for Pallas kernels)."""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

CAMPAIGN_DIR = "experiments/campaigns"

# default graph-level mode set for the measured and analytic probes
DEFAULT_GRAPH_MODES = ("fp_add32", "mxu_fma128", "vmem_ld", "hbm_stream")


def build_step_region(arch: str, kind: str, modes: Sequence[str], *,
                      seq: int, batch: int):
    """The graph-level model-step RegionTarget the measured probe and
    "step" fleet TargetSpecs share: reduced (smoke) config, host backend,
    noise injected around the whole jitted train/decode step."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.core import step_region
    from repro.core.noise import NoiseScale, make_modes
    from repro.models.model import build

    registry = make_modes(NoiseScale(hbm_mib=32, chase_len=1 << 20))
    unknown = [m for m in modes if m not in registry]
    if unknown:
        raise SystemExit(f"unknown mode(s) {unknown}; available: "
                         f"{', '.join(sorted(registry))}")

    cfg = get_smoke_config(arch)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("probe", kind, seq, batch)

    if kind == "train":
        batch_data = api.dummy_batch(shape)

        def step(p, b):
            return api.loss(p, b)[0]
        args = (params, batch_data)
    else:
        cache = api.decode_init(params, {"tokens": jnp.zeros((batch, 1),
                                                             jnp.int32),
                                         "max_seq": seq})
        toks = jnp.zeros((batch, 1), jnp.int32)

        def step(p, c, t):
            return api.decode_step(p, c, t, jnp.int32(seq // 2))[0]
        args = (params, cache, toks)

    region_name = f"{cfg.name}_{kind}_s{seq}_b{batch}"
    return step_region(region_name, step, args,
                       {m: registry[m] for m in modes})


def _run_adhoc(spec, *, reps: int, store: str | None, fresh: bool,
               workers: int, shard: Optional[tuple[int, int]],
               expect_no_measure: bool,
               header: str, audit: str = "gate",
               quality: str = "gate", backend: str = "pallas") -> None:
    """Build a one-target SweepPlan from CLI flags and execute it through
    the fleet worker — the campaign tail (store naming, shard dispatch,
    reporting) lives behind that API now."""
    from repro.fleet.executor import run_worker
    from repro.fleet.plan import SweepPlan

    plan = SweepPlan(name=header, store=store or "", targets=[spec],
                     reps=reps, shards=(shard[1] if shard else 1),
                     workers=workers, backend=backend)
    if not plan.store:
        first = plan.resolve()[0][1][0]
        plan.store = os.path.join(CAMPAIGN_DIR, f"{first.name}.jsonl")
    run_worker(plan, index=(shard[0] if shard else None),
               count=(shard[1] if shard else None), fresh=fresh,
               expect_no_measure=expect_no_measure, header=header,
               audit=audit, quality=quality)


def measured_probe(arch: str, kind: str, modes: list[str], *, seq: int,
                   batch: int, reps: int, store: str | None = None,
                   fresh: bool = False, workers: int = 1,
                   shard: Optional[tuple[int, int]] = None,
                   expect_no_measure: bool = False,
                   audit: str = "gate", quality: str = "gate") -> None:
    """Measured graph-level probe of one model step (smoke config, host
    backend): builds a one-target SweepPlan from the flags and runs it
    through the fleet worker's campaign tail."""
    from repro.core.noise import make_modes

    unknown = [m for m in modes if m not in make_modes()]
    if unknown:
        raise SystemExit(f"unknown mode(s) {unknown}; available: "
                         f"{', '.join(sorted(make_modes()))}")
    from repro.fleet.plan import TargetSpec

    spec = TargetSpec("step", tuple(modes),
                      {"arch": arch, "kind": kind, "seq": seq,
                       "batch": batch})
    _run_adhoc(spec, reps=reps, store=store, fresh=fresh, workers=workers,
               shard=shard, expect_no_measure=expect_no_measure, audit=audit,
               quality=quality,
               header=f"measured probe: {arch} {kind} seq={seq} "
                      f"batch={batch}")


def serve_probe(arch: str, modes: list[str], *, slots: int, prompt: int,
                max_new: int, reps: int, store: str | None = None,
                fresh: bool = False, workers: int = 1,
                shard: Optional[tuple[int, int]] = None,
                expect_no_measure: bool = False,
                audit: str = "gate", quality: str = "gate") -> None:
    """Measured probe of the paged serving engine (smoke config, host
    backend): one plan, TWO regions — the engine's batched prefill and its
    decode tick (``repro.serve.load.build_serve_regions``) — so prefill and
    decode classify separately under the same campaign store."""
    from repro.core.noise import make_modes

    unknown = [m for m in modes if m not in make_modes()]
    if unknown:
        raise SystemExit(f"unknown mode(s) {unknown}; available: "
                         f"{', '.join(sorted(make_modes()))}")
    from repro.fleet.plan import TargetSpec

    spec = TargetSpec("serve", tuple(modes),
                      {"arch": arch, "slots": slots, "prompt": prompt,
                       "max_new": max_new})
    _run_adhoc(spec, reps=reps, store=store, fresh=fresh, workers=workers,
               shard=shard, expect_no_measure=expect_no_measure, audit=audit,
               quality=quality,
               header=f"serve probe: {arch} slots={slots} prompt={prompt}")


def pallas_probe(kernel: str, modes: Optional[list[str]], *, reps: int,
                 n: Optional[int] = None, store: str | None = None,
                 fresh: bool = False, workers: int = 1,
                 shard: Optional[tuple[int, int]] = None,
                 expect_no_measure: bool = False,
                 audit: str = "gate", quality: str = "gate",
                 backend: str = "pallas") -> None:
    """Run the paper's methodology against a real Pallas kernel (on the
    TPU, or in the interpreter with ``backend="interpret"``). The sweep
    delivers k at run time: one Pallas executable per (kernel, mode),
    payload check included."""
    from repro.kernels.region import KERNEL_MODES, SIZE_DEFAULT, validate_size

    if kernel not in KERNEL_MODES:
        raise SystemExit(f"unknown pallas kernel {kernel!r}; one of "
                         f"{', '.join(sorted(KERNEL_MODES))}")
    modes = modes or list(KERNEL_MODES[kernel])
    unknown = [m for m in modes if m not in KERNEL_MODES[kernel]]
    if unknown:
        raise SystemExit(f"kernel {kernel!r} supports modes "
                         f"{KERNEL_MODES[kernel]}, not {unknown}")
    if n is not None:
        try:
            validate_size(kernel, n)
        except ValueError as e:
            raise SystemExit(f"--pallas-n: {e}")
    from repro.fleet.plan import TargetSpec

    spec = TargetSpec("pallas", tuple(modes),
                      {"kernel": kernel,
                       "sizes": [n if n is not None else
                                 SIZE_DEFAULT[kernel]]})
    _run_adhoc(spec, reps=reps, store=store, fresh=fresh, workers=workers,
               shard=shard, expect_no_measure=expect_no_measure, audit=audit,
               quality=quality, backend=backend,
               header=f"pallas probe: {kernel}")


def plan_probe(plan_path: str, *, shard: Optional[tuple[int, int]],
               fresh: bool, expect_no_measure: bool,
               audit: str = "gate", quality: str = "gate") -> None:
    """The fleet worker entry: execute (a shard of) a saved SweepPlan."""
    from repro.fleet.executor import FleetError, run_worker
    from repro.fleet.plan import PlanError, SweepPlan

    try:
        plan = SweepPlan.load(plan_path)
    except (OSError, ValueError) as e:       # PlanError is a ValueError
        raise SystemExit(f"--plan {plan_path}: {e}")
    try:
        run_worker(plan, index=(shard[0] if shard else None),
                   count=(shard[1] if shard else None), fresh=fresh,
                   expect_no_measure=expect_no_measure, audit=audit,
                   quality=quality)
    except (FleetError, PlanError) as e:
        raise SystemExit(str(e))


def analytic_probe(arch: str, shape_name: str, dryrun_dir: str,
                   modes: list[str], *, tol: float, store: str | None = None,
                   fresh: bool = False, expect_no_measure: bool = False
                   ) -> None:
    """Analytic probe of one (arch, shape) dry-run cell: push its roofline
    terms through the saturation model as a resumable prediction campaign
    (``pred`` records replay byte-identically on re-run)."""
    from repro.configs import TPU_V5E, canonical
    from repro.core import AnalyticCampaign, StepTerms, classify
    from repro.core.analytic import pattern_deltas
    from repro.core.noise import make_modes
    from repro.fleet.executor import finish_stats

    cell = os.path.join(dryrun_dir, f"{canonical(arch)}_{shape_name}.json")
    with open(cell) as f:
        rec = json.load(f)
    if rec.get("status") != "ok":
        raise SystemExit(f"dry-run cell {cell} status={rec.get('status')}")
    r = rec["roofline"]
    terms = StepTerms(compute=r["t_compute"], memory=r["t_memory"],
                      ici=r["t_ici"])
    registry = make_modes()
    region_name = f"{canonical(arch)}_{shape_name}"
    store = store or os.path.join(CAMPAIGN_DIR, f"{region_name}_pred.jsonl")
    if fresh and os.path.exists(store):
        os.unlink(store)
    camp = AnalyticCampaign(store, hw=TPU_V5E, tol=tol, k_max=1 << 44)
    print(f"== analytic probe: {arch} {shape_name} [{rec['mesh']}] "
          f"(terms from dry-run: Tc={terms.compute*1e3:.2f}ms "
          f"Tm={terms.memory*1e3:.2f}ms Ti={terms.ici*1e3:.2f}ms, "
          f"dominant={r['dominant']}; campaign store: {store})")
    t0 = terms.bound()

    def classify_fracs(results) -> "object":
        # absorbed-work fraction: what share of the step time each mode's
        # noise occupies before detection — the step-scale-free absorption
        # (bound resource ~= tol; slack resources >> tol)
        fracs = {}
        for m, res in results.items():
            delta = max(pattern_deltas(registry[m], TPU_V5E).values())
            fracs[m] = 100.0 * res.fit.k1 * delta / t0
        return classify(fracs, low=2.0 * 100 * tol, high=6.0 * 100 * tol)

    rep = camp.characterize(region_name, terms,
                            {m: registry[m] for m in modes},
                            classify_fn=classify_fracs)
    for m, res in rep.results.items():
        delta = max(pattern_deltas(registry[m], TPU_V5E).values())
        frac = 100.0 * res.fit.k1 * delta / t0
        print(f"  {m:14s} Abs^raw={res.fit.k1:14.0f} patterns "
              f"(~{frac:6.1f}% of step absorbable)")
    print(f"  => {rep.bottleneck}")
    finish_stats(camp.stats, expect_no_measure)


def _parse_shard(text: str) -> tuple[int, int]:
    try:
        idx, cnt = (int(p) for p in text.split("/"))
    except ValueError:
        raise SystemExit(f"--shard wants I/N (e.g. 0/2), got {text!r}")
    if not (0 <= idx < cnt):
        raise SystemExit(f"--shard index {idx} not in [0, {cnt})")
    return idx, cnt


def build_parser() -> argparse.ArgumentParser:
    """The probe CLI's argparse tree (exposed for help/doc tests)."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.probe",
        description="noise-injection bottleneck probe (measured, analytic, "
                    "pallas-kernel, and fleet-worker modes)")
    ap.add_argument("--arch", default=None,
                    help="model architecture (required unless --pallas or "
                         "--plan)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced smoke config (measured mode always uses "
                         "it; flag kept for explicitness)")
    ap.add_argument("--kind", default="train", choices=("train", "decode"),
                    help="which model step to probe")
    ap.add_argument("--shape", default="train_4k",
                    help="dry-run shape cell to read under --analytic")
    ap.add_argument("--analytic", action="store_true",
                    help="predict absorption from the dry-run roofline "
                         "terms instead of measuring")
    ap.add_argument("--plan", default=None, metavar="PLAN.json",
                    help="execute a repro.fleet SweepPlan: with --shard I/N "
                         "measure that slice into its worker store (the "
                         "fleet worker entry; launchers hand it the "
                         "REPRO_FLEET_EXPECT_DIGEST/REPRO_FLEET_HOST "
                         "handshake env); without, run the whole plan "
                         "in-process, classify, and write the report")
    ap.add_argument("--serve", action="store_true",
                    help="probe the paged serving engine instead of a bare "
                         "model step: two regions (batched prefill + decode "
                         "tick) under one campaign; --seq is the prompt "
                         "length, --batch the slot count")
    ap.add_argument("--max-new", type=int, default=8,
                    help="decode budget per request of the probed serve "
                         "workload (--serve)")
    ap.add_argument("--pallas", default=None,
                    metavar="{matmul,spmxv,attention,probe}",
                    help="probe a Pallas kernel region instead of a model "
                         "step (modes default to the kernel's fp/mxu/vmem "
                         "set)")
    ap.add_argument("--backend", default="pallas",
                    choices=("pallas", "interpret"),
                    help="Pallas backend under --pallas: pallas compiles "
                         "for the TPU and refuses to run without one; "
                         "interpret runs the Pallas interpreter")
    ap.add_argument("--pallas-n", type=int, default=None,
                    help="kernel size knob (rows for matmul/spmxv, seq for "
                         "attention, grid steps for probe)")
    ap.add_argument("--dryrun-dir", default="experiments/dryrun/16x16",
                    help="where the dry-run artifact cells live "
                         "(--analytic)")
    ap.add_argument("--modes", default=None,
                    help="noise modes (default: "
                         f"{','.join(DEFAULT_GRAPH_MODES)}, or the "
                         "kernel's fp/mxu/vmem set under --pallas)")
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length of the probed step")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size of the probed step")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing repetitions per measured point")
    ap.add_argument("--tol", type=float, default=0.05,
                    help="absorption-fit detection tolerance (--analytic)")
    ap.add_argument("--store", default=None,
                    help="campaign JSONL path (default: derived under "
                         f"{CAMPAIGN_DIR}/)")
    ap.add_argument("--fresh", action="store_true",
                    help="discard any existing campaign store first")
    ap.add_argument("--workers", type=int, default=1,
                    help="fan independent mode sweeps over N threads")
    ap.add_argument("--shard", default=None, metavar="I/N",
                    help="measure only worker I's slice of the grid into a "
                         "per-worker store (multi-host fan-out; N must "
                         "match the plan's shards under --plan)")
    ap.add_argument("--expect-no-measure", action="store_true",
                    help="exit non-zero if any fresh measurement was needed "
                         "(assert a merged/complete store replays fully)")
    ap.add_argument("--audit", default="gate",
                    choices=("gate", "warn", "off"),
                    help="static noise-audit policy for whole-plan/ad-hoc "
                         "runs (shards never audit): gate (default) refuses "
                         "statically-dead pairs before measuring, warn "
                         "measures anyway, off skips the audit")
    ap.add_argument("--quality", default="gate",
                    choices=("gate", "warn", "off"),
                    help="runtime measurement-quality policy for whole-plan/"
                         "ad-hoc runs: gate (default) refuses a majority-"
                         "quarantined classification, warn reports it, off "
                         "attaches no quality evidence (only plans that "
                         "declare a quality policy guard their measurements)")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> None:
    """CLI entry: route the flags to the measured / analytic / pallas /
    fleet-worker probe path."""
    from repro.compile_cache import setup_compile_cache

    args = build_parser().parse_args(argv)
    setup_compile_cache()

    modes = ([m.strip() for m in args.modes.split(",") if m.strip()]
             if args.modes else None)
    shard = _parse_shard(args.shard) if args.shard is not None else None
    if args.plan is not None:
        # the plan overrides ALL of these; silently ignoring one would let a
        # user believe they changed the measurement settings
        overridden = [flag for flag, given in (
            ("--arch", args.arch), ("--pallas", args.pallas),
            ("--serve", args.serve),
            ("--analytic", args.analytic), ("--modes", modes),
            ("--store", args.store), ("--reps", args.reps != 3),
            ("--workers", args.workers != 1),
            ("--kind", args.kind != "train"), ("--seq", args.seq != 128),
            ("--batch", args.batch != 4),
            ("--backend", args.backend != "pallas")) if given]
        if overridden:
            raise SystemExit("--plan carries its own targets, modes and "
                             "settings; drop the conflicting flag(s): "
                             + ", ".join(overridden))
        plan_probe(args.plan, shard=shard, fresh=args.fresh,
                   expect_no_measure=args.expect_no_measure,
                   audit=args.audit, quality=args.quality)
        return
    if args.pallas is not None:
        if args.analytic or args.serve:
            raise SystemExit("--pallas excludes --analytic and --serve")
        pallas_probe(args.pallas, modes, reps=args.reps, n=args.pallas_n,
                     store=args.store, fresh=args.fresh,
                     workers=args.workers, shard=shard,
                     expect_no_measure=args.expect_no_measure,
                     audit=args.audit, quality=args.quality,
                     backend=args.backend)
        return
    if args.arch is None:
        raise SystemExit("--arch is required unless --pallas or --plan "
                         "is given")
    if args.serve:
        if args.analytic:
            raise SystemExit("--serve and --analytic are mutually exclusive")
        serve_probe(args.arch, modes or list(DEFAULT_GRAPH_MODES),
                    slots=args.batch, prompt=args.seq, max_new=args.max_new,
                    reps=args.reps, store=args.store, fresh=args.fresh,
                    workers=args.workers, shard=shard,
                    expect_no_measure=args.expect_no_measure,
                    audit=args.audit, quality=args.quality)
        return
    if args.analytic:
        if shard is not None:
            raise SystemExit("--shard applies to measured mode only "
                             "(predictions are too cheap to fan out)")
        analytic_probe(args.arch, args.shape, args.dryrun_dir,
                       modes or list(DEFAULT_GRAPH_MODES),
                       tol=args.tol, store=args.store, fresh=args.fresh,
                       expect_no_measure=args.expect_no_measure)
    else:
        measured_probe(args.arch, args.kind,
                       modes or list(DEFAULT_GRAPH_MODES),
                       seq=args.seq, batch=args.batch, reps=args.reps,
                       store=args.store, fresh=args.fresh,
                       workers=args.workers, shard=shard,
                       expect_no_measure=args.expect_no_measure,
                       audit=args.audit, quality=args.quality)


if __name__ == "__main__":
    main()
