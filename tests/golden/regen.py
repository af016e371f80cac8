"""Regenerate the golden-signature and golden-HLO-audit fixtures.

    PYTHONPATH=src python tests/golden/regen.py

Writes ``signatures.jsonl`` (a campaign store of tiny fixed-seed synthetic
absorption signatures — one region per paper-style bottleneck class, with
curves drawn from the three-phase model plus deterministic jitter) and
``expected.json`` (the fit fields and BottleneckReport each region must
replay to). ``tests/test_golden_signatures.py`` replays the store through
the Campaign engine and compares against ``expected.json`` — a refactor of
curve assembly, the hinge fit, or the classifier that changes any signature
fails loudly instead of silently reclassifying.

Also writes ``hlo/*.txt.gz`` — optimized-HLO dumps (clean / K_LO / K_HI
static compiles) for every Pallas kernel plus a loop region — and
``audit_expected.json``, the exact ``AuditReport`` each trio must audit to.
``tests/test_analysis.py`` replays the checked-in texts through
``repro.analysis.audit_texts`` (pure text -> verdict, no compiler), so a
change to the census, the corruption detectors, or the resource tagging
fails loudly instead of silently re-verdicting. Compiled-HLO fixtures are
pin-dependent only at REGEN time; the replay itself never compiles.

Also writes ``regimes.json`` — the SPMXV regime-transition map: the
spmv_ell swap-probability sweep under per-q forced synthetic clocks
(``tests/test_regimes.py`` owns the sweep; this script just persists its
output), pinning each q's label/confidence/Abs^raw and hence where the
verdict crosses from compute through mixed into l1.

Regenerate ONLY when a change to curve assembly / fitting / classification
/ the audit pass / the regime-transition model is intentional, and say so
in the commit that updates these files.

NOTE (measurement-integrity guard): the runtime quality guard grew the
store schema — "quality" records, an optional "spread" on points and
"sentinels" on done markers — but these goldens are deliberately left
byte-identical: they are synthesized without a quality policy, so the new
fields never appear and every curve/fit/classify expectation is unchanged.
``test_golden_store_is_policyless_and_guard_invariant`` pins exactly that.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

HERE = os.path.dirname(os.path.abspath(__file__))
STORE = os.path.join(HERE, "signatures.jsonl")
EXPECTED = os.path.join(HERE, "expected.json")

SEED = 20260731
REPS = 2          # meta settings the replaying Controller must match
JITTER = 0.003    # multiplicative noise on each point (deterministic rng)

KS = [0, 1, 2, 4, 8, 12, 16, 24, 32, 48, 64]

# region -> (expected label, drift factor recorded in "done",
#            {mode: (t0_seconds, k1_knee, slope_fraction_per_pattern)})
# Mode vocabularies deliberately mix loop-level, graph-level and Pallas
# kernel-level names so the suite pins ALL THREE against the classifier's
# alias table.
REGIONS = {
    "golden_compute": ("compute", None, {            # HACCmk row (loop vocab)
        "fp_add": (2.0e-3, 0.0, 0.30),
        "l1_ld": (2.0e-3, 13.0, 0.20),
        "mem_ld": (2.0e-3, 30.0, 0.15),
    }),
    "golden_bandwidth": ("bandwidth", 1.10, {        # STREAM row (graph vocab)
        "fp_add32": (5.0e-3, 48.0, 0.25),
        "vmem_ld": (5.0e-3, 9.0, 0.22),
        "hbm_stream": (5.0e-3, 1.0, 0.40),
    }),
    "golden_latency": ("latency", None, {            # lat_mem_rd (graph vocab)
        "fp_add32": (1.0e-3, 40.0, 0.20),
        "hbm_stream": (1.0e-3, 11.0, 0.18),
    }),
    "golden_overlap": ("overlap", None, {            # Table 3 case 3
        "fp_add": (3.0e-3, 0.0, 0.35),
        "l1_ld": (3.0e-3, 1.0, 0.30),
    }),
    "golden_ici": ("ici", None, {                    # TPU extension
        "ici_allreduce": (8.0e-3, 1.0, 0.30),
        "fp_add32": (8.0e-3, 14.0, 0.20),
        "vmem_ld": (8.0e-3, 12.0, 0.20),
    }),
    "golden_mixed": ("mixed", None, {                # Table 3 case 4
        "fp_add": (4.0e-3, 8.0, 0.12),
        "l1_ld": (4.0e-3, 7.0, 0.12),
    }),
    "golden_pallas_lsu": ("l1", 1.05, {              # Fig 4a -O0 matmul row
        "fp": (1.5e-3, 30.0, 0.18),                  # (Pallas kernel vocab)
        "vmem": (1.5e-3, 1.0, 0.35),
    }),
}


def synth_ts(rng: np.random.Generator, t0: float, k1: float,
             slope_frac: float) -> list[float]:
    """Three-phase model samples: flat to k1, then linear, ±JITTER."""
    ts = []
    for k in KS:
        t = t0 * (1.0 + max(0.0, k - k1) * slope_frac)
        ts.append(float(t * (1.0 + rng.uniform(-JITTER, JITTER))))
    return ts


def build_store() -> list[dict]:
    rng = np.random.default_rng(SEED)
    records: list[dict] = []
    for region, (_, drift, modes) in REGIONS.items():
        records.append({"kind": "region", "region": region, "body_size": 24})
        for mode, (t0, k1, slope) in modes.items():
            ts = synth_ts(rng, t0, k1, slope)
            records.append({"kind": "meta", "region": region, "mode": mode,
                            "reps": REPS, "compile_once": False})
            records.append({"kind": "sens", "region": region, "mode": mode,
                            "value": ts[-1] / ts[0]})
            for k, t in zip(KS, ts):
                records.append({"kind": "point", "region": region,
                                "mode": mode, "k": k, "t": t})
            records.append({"kind": "done", "region": region, "mode": mode,
                            "ks": KS, "stopped_early": False,
                            "drift": drift, "payload": None})
    return records


def replay(store_path: str) -> dict:
    from repro.core import Campaign, Controller, RegionTarget

    def _fail(*a, **k):
        raise AssertionError("golden replay must never build or measure")

    out = {}
    for region, (label, _, modes) in REGIONS.items():
        camp = Campaign(store_path, Controller(reps=REPS,
                                               verify_payload=False))
        target = RegionTarget(name=region, build=_fail, args_for=_fail)
        rep = camp.characterize(target, list(modes))
        assert camp.stats.measured == 0, region
        assert rep.bottleneck.label == label, (
            f"{region}: synthetic signature classified as "
            f"{rep.bottleneck.label!r}, wanted {label!r} — retune REGIONS")
        out[region] = {
            "label": rep.bottleneck.label,
            "confidence": rep.bottleneck.confidence,
            "body_size": rep.body_size,
            "modes": {m: {f: getattr(r.fit, f) for f in
                          ("k1", "k2", "t0", "slope", "k1_threshold", "sse")}
                      for m, r in rep.results.items()},
        }
    return out


# ---------------------------------------------------------------------------
# Golden HLO audit fixtures: all four Pallas kernels + one loop region.
# Small sizes keep the gzipped texts a few hundred KB total; `interpret`
# keeps the compiles host-runnable without a chip.
# ---------------------------------------------------------------------------

HLO_DIR = os.path.join(HERE, "hlo")
AUDIT_EXPECTED = os.path.join(HERE, "audit_expected.json")
REGIMES_JSON = os.path.join(HERE, "regimes.json")


def build_regime_map() -> dict:
    """Delegate to tests/test_regimes.py's sweep (the harness owns the
    forced-shape model; regen only persists what it produces)."""
    import tempfile

    sys.path.insert(0, os.path.join(HERE, ".."))
    import test_regimes

    prior = os.environ.get("REPRO_SYNTH_MEASURE")
    os.environ["REPRO_SYNTH_MEASURE"] = test_regimes.BASE_S
    try:
        with tempfile.TemporaryDirectory() as d:
            return test_regimes.sweep_regime_map(
                os.path.join(d, "regimes.jsonl"))
    finally:
        if prior is None:
            del os.environ["REPRO_SYNTH_MEASURE"]
        else:
            os.environ["REPRO_SYNTH_MEASURE"] = prior


def _audit_targets():
    from repro.bench.kernels import stream_region
    from repro.kernels.region import pallas_region

    return [
        (pallas_region("probe", backend="interpret", n_steps=8), ["fp"]),
        (pallas_region("matmul", backend="interpret", n=256), ["mxu"]),
        (pallas_region("attention", backend="interpret", seq=64), ["vmem"]),
        (pallas_region("spmxv", backend="interpret", n=256), ["fp"]),
        (stream_region(n=4096, chunk=512), ["fp_add", "mem_ld"]),
    ]


def _write_gz(name: str, text: str) -> None:
    import gzip

    # fixed mtime=0 so a content-identical regen is byte-identical in git
    with open(os.path.join(HLO_DIR, name), "wb") as raw:
        with gzip.GzipFile(fileobj=raw, mode="wb", mtime=0) as f:
            f.write(text.encode())


def build_audit_fixtures() -> list[dict]:
    from repro.analysis import audit_texts, compile_text, compile_texts
    from repro.core.controller import _default_target

    os.makedirs(HLO_DIR, exist_ok=True)
    entries = []
    for target, modes in _audit_targets():
        clean = compile_text(target, "", 0)
        _write_gz(f"{target.name}__clean.txt.gz", clean)
        for mode in modes:
            _, lo, hi = compile_texts(target, mode, clean_text=clean)
            _write_gz(f"{target.name}__{mode}__lo.txt.gz", lo)
            _write_gz(f"{target.name}__{mode}__hi.txt.gz", hi)
            tgt = target.payload_target.get(mode, _default_target(mode))
            rep = audit_texts(clean, lo, hi, region=target.name, mode=mode,
                              target=tgt, hint=target.audit_hint)
            assert rep.verdict == "intact", (
                f"golden fixture must audit intact, got: {rep.explain()} — "
                "the kernel or the audit regressed; fix before regenerating")
            entries.append({"region": target.name, "mode": mode,
                            "target": tgt, "hint": dict(target.audit_hint),
                            "report": rep.to_dict()})
    return entries


def main() -> None:
    records = build_store()
    with open(STORE, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    expected = replay(STORE)
    with open(EXPECTED, "w") as f:
        json.dump(expected, f, indent=1, sort_keys=True)
        f.write("\n")
    n_modes = sum(len(m) for _, _, m in REGIONS.values())
    print(f"wrote {STORE} ({len(records)} records, {len(REGIONS)} regions, "
          f"{n_modes} signatures) and {EXPECTED}")
    audits = build_audit_fixtures()
    with open(AUDIT_EXPECTED, "w") as f:
        json.dump(audits, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {HLO_DIR}/*.txt.gz and {AUDIT_EXPECTED} "
          f"({len(audits)} audited pairs)")
    regimes = build_regime_map()
    with open(REGIMES_JSON, "w") as f:
        json.dump(regimes, f, indent=1)     # sweep order matters: no sort
        f.write("\n")
    print(f"wrote {REGIMES_JSON} ({len(regimes)} q-cells)")


if __name__ == "__main__":
    main()
