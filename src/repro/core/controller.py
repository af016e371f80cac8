"""Noise controller — the paper's high-level tool (§3.1/§3.2) that automates
the injection experiments: sensitivity probing, adaptive sweeps, online
saturation detection, execution clustering, payload verification, and
classification.

The paper's controller rebuilds the target application per (mode, k) — its own
criteria table concedes the cost ("Fast: ✗"). This controller escapes it: the
noise quantity k is a RUNTIME operand of one jitted executable per (region,
mode) (``RegionTarget.build_rt``), so a whole k-sweep compiles O(1)
executables instead of O(len(ks)). That is the only way a sweep delivers k:
a region without ``build_rt`` can be replayed from a campaign store but not
measured. The paper's mitigations still apply (probe first with one or two
quantities; coarse steps of 5–10 for robust loops; stop the sweep online once
saturation is evident).
"""
from __future__ import annotations

import dataclasses
import json
import logging
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.absorption import (AbsorptionCurve, AbsorptionFit, absorption,
                                   floor_time, measure, sweep)
from repro.core.classifier import HIGH, LOW, BottleneckReport, classify
from repro.core.loopnoise import LoopNoise, make_loop_modes
from repro.core import payload as payload_mod
from repro.spans import span

log = logging.getLogger("repro.controller")


class PayloadError(RuntimeError):
    """A (region, mode) pair's noise payload did not verify."""


@dataclasses.dataclass(frozen=True)
class RegionTarget:
    """One noisable region (the paper: a loop nest selected by pragma/config).

    ``build(mode_name, k)`` returns a static-k build and
    ``args_for(mode_name, k)`` its arguments. ``build("", 0)`` must be the
    clean reference. ``body_size``: |l1.l2| for Abs^rel; 0 = derive from HLO.
    Static builds serve what needs a k baked into the HLO: the clean
    reference, the body size, the static audit and the default payload
    check. No sweep times them.

    ``build_rt(mode_name)`` returns ONE jitted callable taking ``(k,
    *args_for_rt(mode_name))`` with k an int32 runtime operand; every sweep
    times it. A target without ``build_rt`` (a store replay that must never
    build) is refused by the Controller the moment a sweep would measure.

    ``payload_check(mode_name, k)`` (optional) overrides the default
    HLO-scope-counting payload verification with a region-specific static
    check — Pallas regions use it to compare the noise accumulator against
    its exact oracle (scope metadata does not survive Pallas lowering).

    ``audit_hint`` (optional) parameterizes the static noise audit
    (``repro.analysis``): ``scoped`` — noise ops carry the named-scope tag
    in optimized HLO (graph/loop regions; Pallas bodies do not);
    ``in_loop`` — patterns are emitted inside the region's loop body, so
    the audit checks for loop-invariant hoisting / fusion-into-consumer;
    ``steps`` — per-sweep-point executions of the noise body.
    """
    name: str
    build: Callable[[str, int], Callable]
    args_for: Callable[[str, int], tuple]
    body_size: int = 0
    payload_target: dict[str, str] = dataclasses.field(default_factory=dict)
    build_rt: Optional[Callable[[str], Callable]] = None
    args_for_rt: Optional[Callable[[str], tuple]] = None
    payload_check: Optional[Callable[[str, int], object]] = None
    audit_hint: Optional[dict] = None


@dataclasses.dataclass
class ModeResult:
    mode: str
    curve: AbsorptionCurve
    fit: AbsorptionFit
    injection: Optional[payload_mod.InjectionReport] = None

    def row(self) -> dict:
        return {
            "mode": self.mode,
            "abs_raw": self.fit.k1,
            "abs_threshold": self.fit.k1_threshold,
            "k2": self.fit.k2,
            "t0_s": self.fit.t0,
            "slope_s_per_pattern": self.fit.slope,
            "ks": self.curve.ks,
            "ts": self.curve.ts,
            "payload_survival": (self.injection.survival_fraction
                                 if self.injection else None),
            "payload_overhead": (self.injection.overhead_fraction
                                 if self.injection else None),
        }


@dataclasses.dataclass
class RegionReport:
    region: str
    results: dict[str, ModeResult]
    bottleneck: BottleneckReport
    body_size: int

    def absorptions(self, *, relative: bool = False) -> dict[str, float]:
        if relative and self.body_size:
            return {m: r.fit.rel(self.body_size) for m, r in self.results.items()}
        return {m: r.fit.k1 for m, r in self.results.items()}

    def to_json(self) -> str:
        bn = {
            "label": self.bottleneck.label,
            "confidence": self.bottleneck.confidence,
            "explanation": self.bottleneck.explanation,
        }
        # static audit evidence serializes only when attached — non-audited
        # reports stay byte-identical to pre-audit output
        if getattr(self.bottleneck, "evidence", None):
            bn["evidence"] = self.bottleneck.evidence
        # likewise runtime measurement-quality evidence: attached only when
        # a quality guard found something to say, so clean runs' reports
        # stay byte-identical to unguarded ones
        if getattr(self.bottleneck, "quality", None):
            bn["quality"] = self.bottleneck.quality
        return json.dumps({
            "region": self.region,
            "body_size": self.body_size,
            "bottleneck": bn,
            "modes": {m: r.row() for m, r in self.results.items()},
        }, indent=2)

    def summary(self) -> str:
        lines = [f"region {self.region!r}  (|body|={self.body_size})"]
        for m, r in self.results.items():
            surv = (f" payload={r.injection.survival_fraction:.0%}"
                    if r.injection else "")
            lines.append(
                f"  {m:12s} Abs^raw={r.fit.k1:7.1f}  Abs^rel="
                f"{r.fit.rel(self.body_size):6.3f}  t0={r.fit.t0*1e3:8.3f}ms"
                f"  slope={r.fit.slope*1e6:8.3f}us/pat{surv}")
        lines.append(f"  => {self.bottleneck}")
        return "\n".join(lines)


class Controller:
    """Runs the §3.2 methodology against a region."""

    def __init__(self, *, tol: float = 0.05, reps: int = 5,
                 probe_k: int = 24, stop_ratio: float = 4.0,
                 verify_payload: bool = True):
        self.tol = tol
        self.reps = reps
        self.probe_k = probe_k            # paper: "values around 20 or 30"
        self.stop_ratio = stop_ratio
        self.verify_payload = verify_payload
        # memoize runtime-k callables per (target, mode): build_rt returns a
        # fresh jit wrapper each call, and jax's compile cache keys on the
        # callable's identity — without this the sensitivity probe and the
        # sweep would each trace their own copy of the SAME program. Keyed
        # by target IDENTITY (two targets may share a name but close over
        # different buffers); the entry pins the target so its id() cannot
        # be recycled onto a stale executable.
        self._rt_cache: dict[tuple[int, str],
                             tuple[RegionTarget, Callable]] = {}

    def _rt_fn(self, target: RegionTarget, mode: str) -> Callable:
        """The region's runtime-k callable; a ValueError for a region that
        has none, before anything is measured."""
        if target.build_rt is None:
            raise ValueError(f"region {target.name!r} has no runtime-k "
                             "build; it can be replayed, not measured")
        key = (id(target), mode)
        if key not in self._rt_cache:
            self._rt_cache[key] = (target, target.build_rt(mode))
        return self._rt_cache[key][1]

    # -- §3.2: one or two quantities first, to learn the sensitivity --------
    def probe_sensitivity(self, target: RegionTarget, mode: str,
                          deadline: Optional[float] = None) -> float:
        reps = max(2, self.reps - 2)
        fn_rt = self._rt_fn(target, mode)
        args = target.args_for_rt(mode)
        t0 = measure(fn_rt, (jnp.int32(0), *args), reps=reps,
                     deadline=deadline)
        tk = measure(fn_rt, (jnp.int32(self.probe_k), *args), reps=reps,
                     deadline=deadline)
        return tk / floor_time(t0, f"probe_sensitivity({target.name}/{mode}) t0")

    def _ks_for(self, sensitivity: float) -> Sequence[int]:
        if sensitivity > 2.0:       # very sensitive: fine steps near zero
            return (0, 1, 2, 3, 4, 6, 8, 12, 16, 24)
        if sensitivity > 1.1:       # moderate
            return (0, 1, 2, 4, 8, 12, 16, 24, 32, 48, 64)
        # robust to noise: steps of 5-10 (paper's guidance), go far
        return (0, 5, 10, 20, 30, 40, 60, 80, 120, 160, 240, 320)

    def run_mode(self, target: RegionTarget, mode: str,
                 ks: Optional[Sequence[int]] = None) -> ModeResult:
        """Sweep one mode. The sensitivity probe and every sweep point reuse
        ONE runtime-k executable; payload verification adds one static-k
        executable for HLO-counted regions (at most 2 compilations for the
        whole sweep) and none for Pallas regions, whose check runs that
        runtime-k executable. A region without ``build_rt`` raises a
        ValueError before anything is measured.

        ``ks``: override the sensitivity-chosen quantities (campaign resume).
        """
        fn_rt = self._rt_fn(target, mode)
        if ks is None:
            ks = self._ks_for(self.probe_sensitivity(target, mode))
        args_rt = target.args_for_rt(mode)
        curve = sweep(fn_rt, mode=mode, ks=ks,
                      args_for=lambda k: (jnp.int32(k), *args_rt),
                      reps=self.reps, stop_ratio=self.stop_ratio)
        fit = absorption(curve, tol=self.tol)
        inj = self.verify_mode_payload(target, mode, curve.ks) \
            if self.verify_payload else None
        return ModeResult(mode=mode, curve=curve, fit=fit, injection=inj)

    def verify_mode_payload(self, target: RegionTarget, mode: str,
                            ks: Sequence[int]):
        """Static payload check (§2.3) on a static-k build — the HLO of the
        runtime-k build holds ONE pattern in a loop body, so surviving ops
        must be counted on a static unrolled trace. Regions with a
        ``payload_check`` override verify their own way instead: Pallas
        kernels hold the runtime-k build's noise accumulator to an exact
        oracle, so their check needs no static build.

        A check that raises, or a report that fails ``ok()``, fails the
        pair: a sweep whose noise did not run measured nothing. Returns None
        only for regions with nothing to verify (a plain, unjitted build, or
        a ``payload_check`` that returns None)."""
        k_chk = next((k for k in reversed(list(ks)) if k), 8)
        with span("campaign.payload_check", mode=mode, k=k_chk):
            if target.payload_check is not None:
                rep = target.payload_check(mode, k_chk)
            else:
                fn = target.build(mode, k_chk)
                if not hasattr(fn, "lower"):
                    return None
                txt = fn.lower(*target.args_for(mode, k_chk)) \
                    .compile().as_text()
                tgt = target.payload_target.get(mode, _default_target(mode))
                rep = payload_mod.analyze_injection(txt, mode=mode,
                                                    target=tgt,
                                                    expected=k_chk)
        if rep is not None and not rep.ok():
            raise PayloadError(f"{target.name}/{mode} k={k_chk}: payload "
                               f"check failed: {rep}")
        return rep

    def characterize(self, target: RegionTarget,
                     modes: Sequence[str] = ("fp_add", "l1_ld", "mem_ld"),
                     *, low: float = LOW, high: float = HIGH) -> RegionReport:
        """Sweep every mode and classify the region; ``low``/``high`` are
        the effective classification thresholds (pass a calibration's
        fitted values — ``repro.core.calibration`` — to classify under
        them; the defaults reproduce the paper constants)."""
        results = {m: self.run_mode(target, m) for m in modes}
        body = target.body_size
        if not body:
            body = derive_body_size(target)
        report = classify({m: r.fit.k1 for m, r in results.items()},
                          low=low, high=high)
        return RegionReport(region=target.name, results=results,
                            bottleneck=report, body_size=body)


def derive_body_size(target: RegionTarget) -> int:
    """|l1.l2| from the clean reference's optimized HLO (0 when the region
    builds a plain callable with nothing to lower)."""
    fn = target.build("", 0)
    if not hasattr(fn, "lower"):
        return 0
    try:
        txt = fn.lower(*target.args_for("", 0)).compile().as_text()
        return payload_mod.body_size(txt)
    except Exception:
        log.warning("body-size derivation failed for %s", target.name,
                    exc_info=True)
        return 0


def _default_target(mode: str) -> str:
    modes = make_loop_modes()
    if mode in modes:
        return modes[mode].target
    return {"fp_add32": "compute", "mxu_fma128": "compute",
            "vmem_ld": "vmem", "hbm_stream": "memory",
            "hbm_latency": "latency",
            # Pallas kernel-level vocabulary (repro.kernels.noise_slots)
            "fp": "compute", "mxu": "compute", "vmem": "vmem",
            }.get(mode, "compute")


def loop_region(name: str, make_fn: Callable[[Optional[LoopNoise], int], Callable],
                args_for: Callable[[], tuple], *, body_size: int = 0,
                rng=None) -> RegionTarget:
    """Adapter for loop-level targets: ``make_fn(noise_or_None, k)`` returns a
    jitted fn whose last positional arg is the noise carry (or no extra arg
    when noise is None).

    The runtime-k build comes for free as long as ``make_fn`` passes its k
    straight through to ``noise.emit(carry, k, i)`` (the documented contract):
    ``build_rt`` hands make_fn a LoopNoise whose emit ignores that static k and
    runs the runtime-k emitter with a k captured from the jitted signature.
    """
    modes = make_loop_modes()
    rng = jax.random.PRNGKey(0) if rng is None else rng
    carries = {m: modes[m].init(rng) for m in modes}

    def build(mode: str, k: int):
        if not mode or k == 0:
            return make_fn(None, 0)
        return make_fn(modes[mode], k)

    def args(mode: str, k: int):
        base = args_for()
        if not mode or k == 0:
            return base
        return (*base, carries[mode])

    def build_rt(mode: str):
        noise = modes[mode]

        def fn(k, *args_and_carry):
            rt_noise = dataclasses.replace(
                noise, emit=lambda nc, _k, i: noise.emit_rt(nc, k, i))
            # the static k=1 handed to make_fn is a placeholder; every
            # pattern is emitted by the runtime-k fori_loop above
            return make_fn(rt_noise, 1)(*args_and_carry)

        return jax.jit(fn)

    def args_rt(mode: str):
        return (*args_for(), carries[mode])

    return RegionTarget(name=name, build=build, args_for=args,
                        body_size=body_size, build_rt=build_rt,
                        args_for_rt=args_rt,
                        audit_hint={"scoped": True, "in_loop": True})
