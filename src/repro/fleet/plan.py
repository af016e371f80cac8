"""SweepPlan — the declarative unit of fleet work.

A plan enumerates the FULL measurement grid up front — regions × modes × ks
× reps × kernel size/q families — and serializes to JSON next to the store,
so every participant (the launcher, each worker subprocess, a human at the
``inspect`` CLI) agrees on exactly the same grid in exactly the same order:

  * ``targets`` is a list of declarative ``TargetSpec``s, not live objects —
    a spec resolves to one or more RegionTargets in whatever process needs
    them (the whole point: a subprocess shard rebuilds its regions from the
    plan file alone);
  * a "pallas" spec spans a whole size/q FAMILY (``kernels.region.
    pallas_family``): one plan — and one campaign store — holds a kernel's
    entire grid;
  * ``pairs()``/``grid()`` fix the canonical (region, mode) enumeration
    (region-major, mode-minor, targets in declaration order). Worker ``i`` of
    ``N`` measures every N-th pair — the same slicing as
    ``Campaign.measure_pairs`` — so the plan file IS the shard assignment;
  * ``digest()`` hashes the canonical JSON; fleet state pins it so a resumed
    fleet can refuse to splice shards measured under a different plan.

Plan JSON (one object, schema-versioned):

  {"sweep_plan": 1, "name": ..., "store": ..., "reps": 2, "shards": 2,
   "workers": 1, "backend": "interpret",
   "targets": [{"kind": "pallas", "modes": ["fp", "vmem"],
                "params": {"kernel": "spmxv", "sizes": [256, 512],
                           "qs": [0.0, 1.0], "nnz_per_row": 16}},
               {"kind": "step", "modes": ["fp_add32", "vmem_ld"],
                "params": {"arch": "gemma_2b", "kind": "train",
                           "seq": 64, "batch": 2}}]}
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Optional

PLAN_SCHEMA = 1


class PlanError(ValueError):
    """A plan file (or plan construction) is invalid."""


@dataclasses.dataclass(frozen=True)
class TargetSpec:
    """One declarative target family: what to measure and under which modes.

    kinds:
      * "pallas" — params {kernel, sizes[, qs, ...spec kwargs]}; resolves via
        ``pallas_family`` to one RegionTarget per size/q;
      * "step"   — params {arch[, kind, seq, batch]}; resolves via
        ``repro.launch.probe.build_step_region`` to one model-step region;
      * "serve"  — params {arch[, slots, prompt, max_new, page_size]}
        (the smoke config), or {arch, layers[, slots, max_seq, page_size,
        prompt_lens, max_new, regions, seed, weights]} (the published config
        cut to ``layers`` layers, its weights read from the directory
        ``weights`` or drawn from ``seed``); resolves via
        ``repro.serve.load.build_serve_target`` to regions of one paged
        serving workload: the engine's batched prefill and its decode tick,
        probed (and classified) separately;
      * "calibrate" — params {[n, chunk]}; resolves via
        ``repro.core.calibration.calibrate_targets`` to the four
        known-regime threshold-calibration regions (synthetic-clock only).
    """
    kind: str
    modes: tuple[str, ...]
    params: dict

    def validate(self) -> None:
        """Reject unknown kinds/modes/params at plan-build time (a bad
        family must not fail later in every worker subprocess)."""
        if not self.modes:
            raise PlanError(f"target {self.kind!r} has no modes")
        if self.kind == "pallas":
            from repro.kernels.region import KERNEL_MODES, check_family_args
            kernel = self.params.get("kernel")
            if kernel not in KERNEL_MODES:
                raise PlanError(f"unknown pallas kernel {kernel!r}; one of "
                                f"{sorted(KERNEL_MODES)}")
            sizes = self.params.get("sizes")
            if not sizes:
                raise PlanError(f"pallas target {kernel!r} needs a non-empty "
                                "sizes list")
            try:
                # full family-argument rules (qs scope, unknown spec params,
                # size alignment) — a bad family must fail at plan BUILD
                # time, not in every worker subprocess at resolve time
                check_family_args(kernel, sizes, self.params.get("qs"),
                                  self._extra_params())
            except ValueError as e:
                raise PlanError(str(e)) from e
            bad = [m for m in self.modes if m not in KERNEL_MODES[kernel]]
            if bad:
                raise PlanError(f"kernel {kernel!r} supports modes "
                                f"{KERNEL_MODES[kernel]}, not {bad}")
        elif self.kind in ("step", "serve"):
            if not self.params.get("arch"):
                raise PlanError(f"{self.kind} target needs an 'arch'")
            from repro.core.noise import make_modes
            bad = [m for m in self.modes if m not in make_modes()]
            if bad:
                raise PlanError(f"unknown graph-level mode(s) {bad}")
            if self.kind == "serve":
                from repro.serve.load import check_serve_params
                try:
                    check_serve_params(self.params)
                except ValueError as e:
                    raise PlanError(str(e)) from e
        elif self.kind == "calibrate":
            from repro.core.calibration import CALIB_MODES
            bad = [m for m in self.modes if m not in CALIB_MODES]
            if bad:
                raise PlanError(f"calibrate targets sweep the loop modes "
                                f"{list(CALIB_MODES)}, not {bad}")
            unknown = sorted(set(self.params) - {"n", "chunk"})
            if unknown:
                raise PlanError(f"unknown calibrate param(s) {unknown}")
            for key in ("n", "chunk"):
                v = self.params.get(key)
                if v is not None and (not isinstance(v, int) or v < 1):
                    raise PlanError(f"calibrate target {key}={v!r}: want a "
                                    "positive int")
        else:
            raise PlanError(f"unknown target kind {self.kind!r}; "
                            "one of ['calibrate', 'pallas', 'step', "
                            "'serve']")

    def _extra_params(self) -> dict:
        return {k: v for k, v in self.params.items()
                if k not in ("kernel", "sizes", "qs")}

    def resolve(self, backend: str = "pallas") -> list:
        """Build this spec's RegionTargets (in the calling process)."""
        if self.kind == "pallas":
            from repro.kernels.region import pallas_family
            return pallas_family(self.params["kernel"], self.params["sizes"],
                                 qs=self.params.get("qs"), backend=backend,
                                 **self._extra_params())
        p = self.params
        if self.kind == "calibrate":
            from repro.core.calibration import calibrate_targets
            return calibrate_targets(n=int(p.get("n", 4096)),
                                     chunk=int(p.get("chunk", 512)))
        if self.kind == "serve":
            from repro.serve.load import build_serve_target
            return build_serve_target(p, self.modes)
        from repro.launch.probe import build_step_region
        return [build_step_region(p["arch"], p.get("kind", "train"),
                                  list(self.modes), seq=int(p.get("seq", 128)),
                                  batch=int(p.get("batch", 4)))]

    def region_names(self) -> list[str]:
        """The names ``resolve()``'s regions will carry, derived WITHOUT
        building anything — grid queries (status, inspect, the launcher's
        completeness checks) must stay cheap even for model-step targets."""
        if self.kind == "pallas":
            from repro.kernels.region import family_names
            return family_names(self.params["kernel"], self.params["sizes"],
                                qs=self.params.get("qs"),
                                **self._extra_params())
        p = self.params
        if self.kind == "calibrate":
            from repro.core.calibration import REGIME_NAMES
            return list(REGIME_NAMES)
        if self.kind == "serve":
            from repro.serve.load import serve_target_names
            return serve_target_names(p)
        from repro.configs import get_smoke_config   # a dataclass, no jax
        return [f"{get_smoke_config(p['arch']).name}_{p.get('kind', 'train')}"
                f"_s{int(p.get('seq', 128))}_b{int(p.get('batch', 4))}"]

    def release(self) -> None:
        """Free what ``resolve`` holds on the device beyond the regions
        themselves: a published serve target's engine."""
        if self.kind == "serve" and "layers" in self.params:
            from repro.serve.load import release_serve_engines
            release_serve_engines()

    def to_dict(self) -> dict:
        """The JSON-able form embedded in a plan's ``targets`` list."""
        return {"kind": self.kind, "modes": list(self.modes),
                "params": self.params}

    @classmethod
    def from_dict(cls, d: dict) -> "TargetSpec":
        """Rebuild a spec from its plan-JSON entry."""
        return cls(kind=d.get("kind", ""), modes=tuple(d.get("modes", ())),
                   params=dict(d.get("params", {})))


@dataclasses.dataclass
class SweepPlan:
    """The full declarative grid plus every setting that shapes measurement
    (reps, compile path, backend) and distribution (shards, threads, and —
    when declared — the launcher and retry policy).

    ``launcher`` (optional) declares HOW shards are spawned:
    ``{"kind": "local"}`` (subprocesses, the default),
    ``{"kind": "ssh", "hosts": [{addr, python, workdir, env}, ...]}``, or
    ``{"kind": "mock", "script": {"0": ["crash"], ...}}`` for deterministic
    fault injection. ``retry`` (optional) declares the ``RetryBudget``:
    ``{"max_attempts": N, "backoff": s, "per_shard_cap": M}``. Both are
    serialized into the digest when set (a different cluster layout or
    retry policy is a different plan identity); when absent, the digest is
    byte-identical to a pre-launcher plan.

    ``store_format`` (optional) selects the campaign-store layout:
    ``"jsonl"`` (one legacy file, the default) or ``"segments"``
    (``repro.core.segments`` — append-only segments + manifest, giving
    incremental merges and ``fleet watch`` live status). Serialized — and
    hashed into the digest — only when set, like launcher/retry.

    ``quality`` (optional) declares the runtime measurement-integrity
    guard: one flat dict of ``repro.core.quality`` QualityPolicy and
    RemeasureBudget fields (e.g. ``{"max_spread": 0.15, "sentinel_every":
    4, "watchdog_floor_s": 0.5, "max_attempts": 2}``). Workers then
    dispersion-gate every fresh point, interleave baseline sentinels,
    quarantine what can't be trusted, and re-measure quarantined points on
    resume. Serialized — and hashed — only when set: measurement validity
    thresholds are part of the plan's identity.
    """
    name: str
    store: str
    targets: list[TargetSpec]
    reps: int = 2
    shards: int = 1
    workers: int = 1
    backend: str = "pallas"
    launcher: Optional[dict] = None
    retry: Optional[dict] = None
    store_format: Optional[str] = None
    quality: Optional[dict] = None

    # -- validation / identity ----------------------------------------------
    def validate(self) -> None:
        """Reject malformed plans (empty grids, bad sizes, unknown modes,
        invalid launcher/retry specs) before they land on disk."""
        if not self.name:
            raise PlanError("plan needs a name")
        if not self.store:
            raise PlanError("plan needs a store path")
        if not self.targets:
            raise PlanError("plan has no targets")
        if self.shards < 1 or self.workers < 1 or self.reps < 1:
            raise PlanError("shards, workers and reps must be >= 1")
        if self.backend not in ("pallas", "interpret"):
            raise PlanError(f"backend {self.backend!r} unknown; one of "
                            "['pallas', 'interpret']")
        for spec in self.targets:
            spec.validate()
        self._validate_distribution()

    def _validate_distribution(self) -> None:
        """Validate the optional launcher/retry specs (lazy import: the
        launchers module sits above plan in the layer order)."""
        from repro.fleet import launchers as ln

        if self.store_format not in (None, "jsonl", "segments"):
            raise PlanError(f"store_format {self.store_format!r} unknown; "
                            "one of ['jsonl', 'segments']")
        if (self.store_format == "segments" and self.launcher is not None
                and self.launcher.get("kind") == "ssh"):
            # the ssh launcher pushes/pulls ONE file per worker store; a
            # segment directory doesn't fit that staging protocol yet
            raise PlanError("store_format 'segments' is not supported with "
                            "the ssh launcher (single-file staging); use "
                            "local/mock, or the default jsonl layout")
        if self.launcher is not None:
            kind = self.launcher.get("kind")
            if kind not in ln.LAUNCHER_KINDS:
                raise PlanError(f"launcher kind {kind!r} unknown; one of "
                                f"{list(ln.LAUNCHER_KINDS)}")
            unknown = sorted(set(self.launcher)
                             - {"kind", "hosts", "script", "in_process"})
            if unknown:
                raise PlanError(f"unknown launcher key(s) {unknown}")
            try:
                if kind == "ssh":
                    hosts = [ln.HostSpec.from_dict(h)
                             for h in self.launcher.get("hosts", [])]
                    if not hosts:
                        raise PlanError("ssh launcher spec needs a "
                                        "non-empty hosts list")
                elif kind == "mock":
                    ln.MockClusterLauncher(self.launcher.get("script"))
            except ln.FleetError as e:
                raise PlanError(str(e)) from e
        if self.retry is not None:
            try:
                ln.RetryBudget.from_dict(self.retry)
            except ln.FleetError as e:
                raise PlanError(str(e)) from e
        if self.quality is not None:
            from repro.core.quality import quality_from_dict
            try:
                quality_from_dict(self.quality)
            except ValueError as e:
                raise PlanError(str(e)) from e

    def to_dict(self) -> dict:
        """The canonical JSON-able form; ``launcher``/``retry`` appear only
        when declared, so plans without them keep their pre-launcher
        digest."""
        d = {"sweep_plan": PLAN_SCHEMA, "name": self.name,
             "store": self.store, "reps": self.reps,
             "shards": self.shards, "workers": self.workers,
             "backend": self.backend,
             "targets": [t.to_dict() for t in self.targets]}
        if self.launcher is not None:
            d["launcher"] = self.launcher
        if self.retry is not None:
            d["retry"] = self.retry
        if self.store_format is not None:
            d["store_format"] = self.store_format
        if self.quality is not None:
            d["quality"] = self.quality
        return d

    def canonical_json(self) -> str:
        """``to_dict`` with sorted keys — the digest's input bytes."""
        return json.dumps(self.to_dict(), sort_keys=True)

    def digest(self) -> str:
        """Content hash pinning the grid AND the measurement settings —
        fleet state refuses to splice shards from a different digest."""
        return hashlib.sha256(self.canonical_json().encode()).hexdigest()[:12]

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> str:
        """Validate, then atomically write the plan JSON (with its digest
        echoed for humans) to ``path``; returns ``path``."""
        self.validate()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({**self.to_dict(), "digest": self.digest()}, f,
                      indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    @classmethod
    def from_dict(cls, d: dict) -> "SweepPlan":
        """Rebuild (and validate) a plan from its JSON object; plans saved
        before the launcher/retry fields existed load unchanged. Older plans
        carry ``"compile_once": true``, which is read and ignored; false
        asked for the trace-per-k sweep, which is gone, and is refused."""
        if d.get("sweep_plan") != PLAN_SCHEMA:
            raise PlanError(f"not a sweep plan (sweep_plan="
                            f"{d.get('sweep_plan')!r}, want {PLAN_SCHEMA})")
        if not d.get("compile_once", True):
            raise PlanError("compile_once: false asks for the trace-per-k "
                            "sweep, which was removed; every sweep takes k "
                            "at run time (drop the key)")
        plan = cls(name=d.get("name", ""), store=d.get("store", ""),
                   targets=[TargetSpec.from_dict(t)
                            for t in d.get("targets", [])],
                   reps=int(d.get("reps", 2)), shards=int(d.get("shards", 1)),
                   workers=int(d.get("workers", 1)),
                   backend=d.get("backend", "pallas"),
                   launcher=d.get("launcher"), retry=d.get("retry"),
                   store_format=d.get("store_format"),
                   quality=d.get("quality"))
        plan.validate()
        return plan

    @classmethod
    def load(cls, path: str) -> "SweepPlan":
        """Load and validate a plan JSON file."""
        with open(path) as f:
            return cls.from_dict(json.load(f))

    # -- the canonical grid --------------------------------------------------
    def resolve(self) -> list[tuple[TargetSpec, list]]:
        """Resolve every spec (cached: a plan resolves once per process, so
        all grid queries see the SAME RegionTarget objects)."""
        if getattr(self, "_resolved", None) is None:
            self._resolved = [(spec, spec.resolve(self.backend))
                              for spec in self.targets]
        return self._resolved

    def release(self) -> None:
        """Free every target's device state (``TargetSpec.release``); the
        fleet calls it when a campaign ends."""
        for spec in self.targets:
            spec.release()

    def pairs(self) -> list[tuple[object, str]]:
        """The full (RegionTarget, mode) grid in canonical order — the exact
        sequence ``Campaign.measure_pairs`` slices across workers."""
        return [(region, mode) for spec, regions in self.resolve()
                for region in regions for mode in spec.modes]

    def grid(self) -> list[tuple[str, str]]:
        """The grid by (region name, mode), WITHOUT resolving targets —
        completeness queries, status and the launcher stay cheap (a step
        target otherwise builds a whole model just to learn its name).
        Same enumeration order as ``pairs()``; pinned by tests."""
        out = [(name, mode) for spec in self.targets
               for name in spec.region_names() for mode in spec.modes]
        if len(set(out)) != len(out):
            raise PlanError(f"plan {self.name!r} enumerates duplicate "
                            "(region, mode) pairs; targets must not overlap")
        return out

    # -- derived paths -------------------------------------------------------
    def worker_stores(self) -> list[str]:
        """Every shard's worker-store path (``store.wIofN.jsonl``)."""
        from repro.core.campaign import worker_store
        return [worker_store(self.store, i, self.shards)
                for i in range(self.shards)]

    def fleet_path(self) -> str:
        """Where this plan's ``fleet.json`` ledger lives."""
        return os.path.splitext(self.store)[0] + ".fleet.json"

    def report_path(self) -> str:
        """Where this plan's canonical ``report.json`` lands."""
        return os.path.splitext(self.store)[0] + ".report.json"
