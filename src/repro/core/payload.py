"""Static payload/overhead verification (paper §2.3).

The paper splits injected instructions into *payload* (the useful noise) and
*overhead* (spills / setup), computed by statically analyzing the compiler's
output, "ensuring that noise did not produce unexpected and significant side
effects that may bias analysis". Here the compiler is XLA: we re-parse the
*optimized* HLO and count surviving instructions whose ``op_name`` metadata
carries the ``noise_pattern`` scope tag.

Graph-level noise cannot spill registers, but XLA can fuse, dedup (CSE), or
reschedule patterns — the exact analogue of "did my noise survive -O3". A
``survival_fraction`` < 1 means patterns were merged and absorption readings
for that (code, mode, k) are biased; the controller re-emits with more chains.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.core.noise import NOISE_SCOPE
from repro.hlo.parse import (Instr, called_comp, find_entry,
                             nesting_multipliers, parse_module)

# Opcodes that are pure plumbing, never counted as payload or overhead.
_BOOKKEEPING = frozenset({
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "copy", "broadcast", "reshape", "transpose", "iota", "after-all",
    "bitcast-convert",
})

# payload opcode families per noise-mode target
PAYLOAD_OPS = {
    "compute": {"add", "multiply", "subtract", "dot", "convolution"},
    "l1": {"dynamic-slice", "gather", "slice"},
    "vmem": {"dynamic-slice", "gather", "slice", "add"},
    "memory": {"dynamic-slice", "gather", "slice"},
    "latency": {"dynamic-slice", "gather"},
    "ici": {"all-reduce", "all-gather", "all-to-all", "reduce-scatter",
            "collective-permute"},
}


@dataclasses.dataclass
class InjectionReport:
    mode: str
    target: str
    expected: int              # k patterns requested (static count)
    payload: int               # surviving payload ops (static)
    overhead: int              # surviving non-payload noise ops
    payload_dynamic: int       # payload weighted by loop trip counts
    body_ops: int              # non-noise ops in the injected loop body |l1.l2|
    # Pallas checks: the kernel's main output against a float32 reference,
    # as max|out - ref| / max|ref|, and the tolerance it is held to
    ref_err: Optional[float] = None
    ref_tol: Optional[float] = None

    @property
    def survival_fraction(self) -> float:
        return self.payload / self.expected if self.expected else 1.0

    @property
    def overhead_fraction(self) -> float:
        tot = self.payload + self.overhead
        return self.overhead / tot if tot else 0.0

    def ok(self, min_survival: float = 0.9, max_overhead: float = 0.5
           ) -> bool:
        """The payload verified: the k patterns survived, overhead stayed
        bounded and, for a Pallas kernel, its main output matched the
        reference. An ICI pattern is one collective, so more collectives
        than patterns fail; it is exempt from the overhead bound, since each
        collective carries its local op (the all-reduce mean's divide, the
        all-gather's row slice) by construction. Other patterns may count
        several payload ops (a VMEM pattern loads and adds)."""
        if self.target == "ici":
            fits = self.payload <= self.expected
        else:
            fits = self.overhead_fraction <= max_overhead
        return (min_survival <= self.survival_fraction and fits
                and (self.ref_tol is None or self.ref_err <= self.ref_tol))


_ASYNC_MARK_RE = re.compile(
    r'custom_call_target="AsyncCollective(Start|Done)"')


def _is_noise(ins: Instr) -> bool:
    return NOISE_SCOPE in ins.op_name


def analyze_injection(compiled_text: str, *, mode: str, target: str,
                      expected: int,
                      fused_inner: bool = True) -> InjectionReport:
    """Count surviving noise ops in optimized HLO.

    ``fused_inner``: on CPU, noise ends up inside fusion computations whose
    instructions are printed as separate computations — count those (the real
    machine ops), not the fusion wrappers.
    """
    comps = parse_module(compiled_text)
    entry = find_entry(comps, compiled_text)
    mult = nesting_multipliers(comps, entry)
    pay_ops = PAYLOAD_OPS.get(target, PAYLOAD_OPS["compute"])
    collectives = PAYLOAD_OPS["ici"]
    # a collective's reduction body is part of the collective, not overhead
    combiners = {called_comp(ins, "to_apply")
                 for instrs in comps.values() for ins in instrs
                 if ins.opcode in collectives}
    # XLA may combine a noise collective with one of the step's into one
    # tuple-shaped op tagged as the step's; its noise-tagged element reads
    # (get-tuple-element) still name it
    by_name = {ins.name: ins for instrs in comps.values() for ins in instrs}
    combined = {ins.operand_names()[0] for instrs in comps.values()
                for ins in instrs
                if ins.opcode == "get-tuple-element" and _is_noise(ins)
                and ins.operand_names()}
    combined = {n for n in combined if n in by_name
                and by_name[n].opcode in pay_ops
                and not _is_noise(by_name[n])}

    payload = overhead = 0
    payload_dyn = 0
    noisy_comps: set[str] = set()
    # the TPU compiler splits an async collective into start, overlapped and
    # done fusions; the start and done fusions hold clones of the op for
    # bookkeeping, marked by their AsyncCollectiveStart/Done custom call
    clones = {cname for cname, instrs in comps.items()
              if any(ins.opcode == "custom-call"
                     and _ASYNC_MARK_RE.search(ins.line) for ins in instrs)}
    for cname, instrs in comps.items():
        if cname in combiners or cname in clones:
            continue
        for ins in instrs:
            if not (_is_noise(ins) or ins.name in combined):
                continue
            if ins.opcode in _BOOKKEEPING or ins.opcode == "fusion":
                continue
            noisy_comps.add(cname)
            if ins.opcode in pay_ops:
                payload += 1
                payload_dyn += mult.get(cname, 1)
            else:
                overhead += 1

    # |l1.l2|: non-noise, non-bookkeeping ops in computations where noise
    # landed (= the target loop body after optimization).
    body_ops = 0
    for cname in noisy_comps:
        for ins in comps[cname]:
            if _is_noise(ins) or ins.opcode in _BOOKKEEPING:
                continue
            body_ops += 1

    return InjectionReport(mode=mode, target=target, expected=expected,
                           payload=payload, overhead=overhead,
                           payload_dynamic=payload_dyn, body_ops=body_ops)


def body_size(compiled_text: str, *, computation_hint: Optional[str] = None
              ) -> int:
    """Instruction count of the hottest loop body |l1.l2| (for Abs^rel when a
    clean (k=0) compile is analyzed — no noise tags to locate the body).

    The hottest body = all computations executing at the maximum loop-nesting
    multiplier (the while body plus the fusion computations it calls — on CPU
    the real work lives inside ``fused_computation.*``)."""
    comps = parse_module(compiled_text)
    if computation_hint and computation_hint in comps:
        return sum(1 for i in comps[computation_hint]
                   if i.opcode not in _BOOKKEEPING)
    entry = find_entry(comps, compiled_text)
    mult = nesting_multipliers(comps, entry)
    inner = {c: m for c, m in mult.items() if m > 1}
    if not inner:
        return sum(1 for i in comps.get(entry, ())
                   if i.opcode not in _BOOKKEEPING)
    mmax = max(inner.values())
    total = 0
    for cname, m in inner.items():
        if m != mmax or "condition" in cname or "cond" in cname.split(".")[0]:
            continue
        total += sum(1 for i in comps[cname]
                     if i.opcode not in _BOOKKEEPING and i.opcode != "fusion")
    return max(total, 1)
