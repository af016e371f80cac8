"""The static noise audit: two-point k-scaling census over optimized HLO.

Counting "did my k patterns survive" on a single noisy compile is brittle:
XLA restructures loop boundaries between a clean and a noisy build, so the
clean-vs-noisy instruction diff carries ±O(1) artifacts that drown a small
k. The audit instead compiles the SAME executable at two static noise
counts (``K_LO``/``K_HI``) and takes the census delta — every instruction
the compiler keeps per extra pattern, with boundary restructuring cancelled
exactly. A third, clean (k=0) compile attributes the corruption class when
the payload died.

Census key is ``(opcode, nesting multiplier, entry|sub)``: computation
names differ between compiles but multipliers (loop trip products) and
entry-ness are structurally stable, so deltas line up. Survival counts the
whole payload family of the mode's target (``core.payload.PAYLOAD_OPS``) —
XLA legitimately CSEs e.g. the loop-invariant dots of an mxu chain while
the carried adds still scale, and family-level counting keeps that pair
honest instead of flagging it dead.

Corruption classes (detected statically, in this order):
  strength_reduction      payload does not scale with k; the hi-vs-clean
                          diff gained a ``multiply`` (k adds -> one a*k)
  constant_folding        payload does not scale; hi-vs-clean gained only
                          constants (the addend was compile-time constant)
  dce                     payload does not scale and left nothing behind
  fusion_into_consumer    payload scales, but lands once (mult 1) inside a
                          sub-computation while the region loops — the
                          noise no longer executes per step
  loop_invariant_hoisting same, but hoisted into the entry computation
  partial_elision         payload scales at < 1 family op per pattern

Verdicts: ``intact`` (>= 1 surviving family op per pattern, placed where
it executes), ``degraded`` (hoisting / fusion / partial), ``dead`` (the
first three classes). Only ``dead`` refuses a fleet plan at the gate.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Optional

from repro.analysis.graph import chain_depth
from repro.analysis.resources import (BANDWIDTH_OPS, TARGET_FAMILY,
                                      predict_direction, pressure_vector)
from repro.core.noise import NOISE_SCOPE
from repro.core.payload import PAYLOAD_OPS
from repro.hlo.parse import find_entry, nesting_multipliers, parse_module

K_LO = 4
K_HI = 12

# container opcodes: their called computations are censused directly
_CONTAINERS = frozenset({"fusion", "call", "while", "conditional"})
# pure plumbing, never part of a payload family (constant IS counted — the
# constant-folding detector keys on constant growth)
_PLUMBING = frozenset({"tuple", "get-tuple-element", "parameter",
                       "after-all"})


class AuditError(RuntimeError):
    """A planned pair could not be audited (build or compile failed)."""


@dataclasses.dataclass
class Census:
    """One compiled module, reduced to audit-comparable aggregates."""
    counts: Counter          # (opcode, mult, where) -> instructions
    bytes: Counter           # (opcode, mult, where) -> result bytes
    load_depth: int          # longest load-family def-use chain (any comp)
    loop_mult: int           # max loop multiplier over censused comps


def take_census(text: str, *, scoped: bool = False) -> Census:
    """Census one optimized HLO module.

    ``scoped``: count only instructions tagged with the ``noise_pattern``
    named-scope (graph/loop regions keep the tag through optimization;
    Pallas kernel bodies carry no scope metadata, so kernel audits census
    everything and rely on the two-point delta to isolate the noise)."""
    comps = parse_module(text)
    entry = find_entry(comps, text)
    mults = nesting_multipliers(comps, entry)
    counts: Counter = Counter()
    nbytes: Counter = Counter()
    load_depth = 0
    loop_mult = 1
    for cname, instrs in comps.items():
        m = mults.get(cname, 0)
        if not m:
            continue
        loop_mult = max(loop_mult, m)
        where = "entry" if cname == entry else "sub"

        def _counted(ins) -> bool:
            return (ins.opcode in BANDWIDTH_OPS
                    and (not scoped or NOISE_SCOPE in ins.op_name))

        load_depth = max(load_depth, chain_depth(instrs, _counted))
        for ins in instrs:
            if ins.opcode in _CONTAINERS or ins.opcode in _PLUMBING:
                continue
            if scoped and NOISE_SCOPE not in ins.op_name:
                continue
            key = (ins.opcode, m, where)
            counts[key] += 1
            nbytes[key] += ins.result_bytes
    return Census(counts=counts, bytes=nbytes, load_depth=load_depth,
                  loop_mult=loop_mult)


def _delta(hi: Counter, lo: Counter) -> dict:
    """Per-key census difference (keys present in either side)."""
    out = {}
    for key in set(hi) | set(lo):
        d = hi.get(key, 0) - lo.get(key, 0)
        if d:
            out[key] = d
    return out


def _family_total(delta: dict, family: set) -> int:
    return sum(n for key, n in delta.items() if key[0] in family)


@dataclasses.dataclass
class AuditReport:
    """Static verdict for one planned (region, mode) pair."""
    region: str
    mode: str
    target: str                  # the mode's declared resource target
    verdict: str                 # intact | degraded | dead
    corruption: Optional[str]    # corruption class when not intact
    survival: float              # surviving payload-family ops per pattern
    resources: dict              # per-pattern pressure vector
    predicted: str               # compute | bandwidth | latency | ici | none
    agrees: Optional[bool]       # predicted direction matches the target?
    k_lo: int = K_LO
    k_hi: int = K_HI
    detail: str = ""             # human-readable census-delta summary

    @property
    def survival_fraction(self) -> float:
        return max(0.0, min(1.0, self.survival))

    @property
    def ok(self) -> bool:
        return self.verdict != "dead"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["survival"] = round(self.survival, 4)
        d["resources"] = {k: round(v, 4)
                          for k, v in sorted(self.resources.items())}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "AuditReport":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def explain(self) -> str:
        """One doctor-facing line: what the compiler did to this pair."""
        why = {
            "strength_reduction":
                "k chained adds were strength-reduced to one multiply "
                "(the addend is loop-invariant to XLA)",
            "constant_folding":
                "the noise payload folded to compile-time constants "
                "(the addend was not a runtime value)",
            "dce":
                "the noise payload was dead-code-eliminated (its result "
                "does not reach a live output)",
            "fusion_into_consumer":
                "the payload fused into a consumer computation that runs "
                "once, not per region step",
            "loop_invariant_hoisting":
                "the payload was hoisted out of the region loop and runs "
                "once, not per step",
            "partial_elision":
                "only part of the payload survives per pattern (CSE or "
                "partial folding)",
        }.get(self.corruption or "", "payload scales instruction-for-"
                                     "instruction with k")
        return (f"{self.region} × {self.mode}: {self.verdict} "
                f"(survival {self.survival_fraction:.0%}/pattern, "
                f"predicts {self.predicted}) — {why}")


def _expects_loop_placement(hint: dict, loop_mult: int) -> bool:
    """Should the payload land at a loop multiplier > 1?

    Only when the region says its noise body executes per loop step AND it
    actually loops: a hint with ``steps`` (Pallas grid size) decides from
    that count — a single-step grid legitimately places noise at mult 1,
    and an unrelated inner loop elsewhere in the module must not trip the
    hoisting detector. Hints without ``steps`` (loop regions) fall back to
    the module's own loop multiplier."""
    if not hint.get("in_loop"):
        return False
    steps = hint.get("steps")
    if steps is not None:
        return steps > 1
    return loop_mult > 1


def audit_texts(clean_text: str, lo_text: str, hi_text: str, *,
                region: str, mode: str, target: str,
                hint: Optional[dict] = None,
                k_lo: int = K_LO, k_hi: int = K_HI) -> AuditReport:
    """Audit one pair from its three compiled-HLO texts (pure; this is the
    layer the golden fixtures pin)."""
    hint = hint or {}
    scoped = bool(hint.get("scoped", False))
    c0 = take_census(clean_text, scoped=scoped)
    clo = take_census(lo_text, scoped=scoped)
    chi = take_census(hi_text, scoped=scoped)

    patterns = k_hi - k_lo
    scale = _delta(chi.counts, clo.counts)          # the k-scaling delta
    scale_bytes = _delta(chi.bytes, clo.bytes)
    vs_clean = _delta(chi.counts, c0.counts)        # for attribution only
    family = PAYLOAD_OPS.get(target, PAYLOAD_OPS["compute"])
    survival = max(0, _family_total(scale, family)) / patterns
    depth_delta = max(0, chi.load_depth - clo.load_depth)

    verdict, corruption = "intact", None
    if survival < 1.0 / patterns:                   # < 1 op across the span
        verdict = "dead"
        n_mult = sum(n for key, n in vs_clean.items()
                     if key[0] == "multiply" and n > 0)
        n_const = sum(n for key, n in vs_clean.items()
                      if key[0] == "constant" and n > 0)
        if target == "compute" and n_mult > 0:
            corruption = "strength_reduction"
        elif n_const > 0:
            corruption = "constant_folding"
        else:
            corruption = "dce"
    elif survival < 1.0:
        verdict, corruption = "degraded", "partial_elision"
    elif (_expects_loop_placement(hint, chi.loop_mult)
          and all(key[1] == 1 for key, n in scale.items()
                  if key[0] in family and n > 0)):
        # scales with k but never inside the loop that defines the region
        verdict = "degraded"
        placed_sub = any(key[2] == "sub" for key, n in scale.items()
                         if key[0] in family and n > 0)
        corruption = ("fusion_into_consumer" if placed_sub
                      else "loop_invariant_hoisting")

    resources = pressure_vector(scale, scale_bytes, depth_delta, patterns)
    predicted = predict_direction(scale, depth_delta, patterns)
    fam = TARGET_FAMILY.get(target)
    agrees = (predicted == fam) if predicted != "none" and fam else None

    pieces = [f"{op}@x{m}{'' if w == 'entry' else '/sub'}:{n:+d}"
              for (op, m, w), n in sorted(scale.items())
              if op in family or n > 0]
    return AuditReport(region=region, mode=mode, target=target,
                       verdict=verdict, corruption=corruption,
                       survival=survival, resources=resources,
                       predicted=predicted, agrees=agrees,
                       k_lo=k_lo, k_hi=k_hi,
                       detail=" ".join(pieces[:12]))


def _check_censusable(target) -> None:
    """A kernel compiled by Mosaic is one opaque ``tpu_custom_call`` in the
    HLO: no census can see its noise, so the pair is unauditable (its
    payload check verifies the noise arithmetically after the sweep)."""
    if (target.audit_hint or {}).get("opaque"):
        raise AuditError(f"{target.name}: the kernel body is compiled by "
                         "Mosaic into one tpu_custom_call, opaque to the HLO "
                         "census; the payload check verifies its noise")


def compile_text(target, mode: str, k: int) -> str:
    """Compile ONE static build of a pair and return its optimized HLO text.
    No measurement happens: the executable is lowered and compiled, never
    run."""
    try:
        fn = target.build(mode, k)
        args = target.args_for(mode, k)
        return fn.lower(*args).compile().as_text()
    except Exception as e:                  # noqa: BLE001 — surfaced as audit
        raise AuditError(f"{target.name} × {mode or 'clean'} (k={k}): static "
                         f"build failed during audit: {e}") from e


def compile_texts(target, mode: str, *, k_lo: int = K_LO, k_hi: int = K_HI,
                  clean_text: Optional[str] = None) -> tuple[str, str, str]:
    """The (clean, k_lo, k_hi) static compiles of one pair. ``clean_text``
    reuses an already-compiled clean module (it is mode-independent, so one
    clean compile serves every mode of a region)."""
    if clean_text is None:
        clean_text = compile_text(target, "", 0)
    return (clean_text, compile_text(target, mode, k_lo),
            compile_text(target, mode, k_hi))


def audit_pair(target, mode: str, *, k_lo: int = K_LO, k_hi: int = K_HI,
               clean_text: Optional[str] = None) -> AuditReport:
    """Audit one (RegionTarget, mode) pair: three static compiles (two when
    ``clean_text`` is shared), zero measurements."""
    from repro.core.controller import _default_target

    _check_censusable(target)
    clean, lo, hi = compile_texts(target, mode, k_lo=k_lo, k_hi=k_hi,
                                  clean_text=clean_text)
    tgt = target.payload_target.get(mode, _default_target(mode))
    return audit_texts(clean, lo, hi, region=target.name, mode=mode,
                       target=tgt, hint=target.audit_hint,
                       k_lo=k_lo, k_hi=k_hi)


def audit_plan(plan, *, skip=frozenset(), on_error=None) -> list[AuditReport]:
    """Audit every (region, mode) pair of a resolved SweepPlan, in plan
    order. The clean (k=0) compile is shared across a region's modes.

    ``skip``: (region, mode) pairs with existing audit records.
    ``on_error``: callback ``(region, mode, AuditError)`` — when given, a
    pair whose static build fails is reported there and skipped instead of
    aborting the whole audit (an unauditable pair is not PROOF of a dead
    payload; the measuring path will surface the real failure)."""
    reports = []
    for spec, targets in plan.resolve():
        for tgt in targets:
            clean: Optional[str] = None
            for mode in spec.modes:
                if (tgt.name, mode) in skip:
                    continue
                try:
                    _check_censusable(tgt)
                    if clean is None:
                        clean = compile_text(tgt, "", 0)
                    reports.append(audit_pair(tgt, mode, clean_text=clean))
                except AuditError as e:
                    if on_error is None:
                        raise
                    on_error(tgt.name, mode, e)
    return reports
