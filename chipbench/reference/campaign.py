"""Plain reference of one noise-injection campaign's analysis, in NumPy.

From the raw records a campaign stored (the sensitivity probe, the timed
points and the drift factor of each (region, mode) pair), it rebuilds what
the method of arXiv:2509.08446 section 3 makes of them: the k grid the
probe selects and how far the online stop rule walks it, the drift-
corrected curve, the three-phase fit and the bottleneck class. It imports
nothing of the program; the constants below are the method's, as the
paper and the repo's documented defaults state them.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PROBE_GRIDS = (            # (sensitivity above, k grid)
    (2.0, (0, 1, 2, 3, 4, 6, 8, 12, 16, 24)),
    (1.1, (0, 1, 2, 4, 8, 12, 16, 24, 32, 48, 64)),
    (float("-inf"), (0, 5, 10, 20, 30, 40, 60, 80, 120, 160, 240, 320)),
)
STOP_RATIO = 4.0           # a point past 4 x t(0) is saturated ...
STOP_CONSECUTIVE = 2       # ... and two in a row end the sweep
FIT_TOL = 0.05             # "within 5 % of t(0)" for the threshold reading
LOW, HIGH = 4.0, 20.0      # patterns: saturated at or under, slack at or over
CHECK_K_MAX = 16           # the payload check runs at most this many patterns
# what ``compare`` counts, each a mismatch the reference finds
MISMATCHES = ("pairs_unverified", "pairs_off_grid", "fits_off",
              "verdicts_off")

# noise mode -> the resource slot it loads (kernel-level vocabulary first)
SLOTS = {
    "fp": ("fp_add", "fp_add32", "fp_fma", "mxu_fma128", "fp_add64", "fp",
           "mxu"),
    "l1": ("l1_ld", "vmem_ld", "l1_ld64", "vmem"),
    "mem": ("mem_ld", "hbm_stream", "memory_ld64"),
    "chase": ("chase", "hbm_latency", "memory_chase"),
}


def read_records(path) -> list:
    """Every record of a JSON-lines campaign store."""
    return [json.loads(line) for line in Path(path).read_text().splitlines()
            if line.strip()]


def pairs_of(records: list) -> dict:
    """``{(region, mode): {"sens", "points" {k: t}, "done"}}``, later
    records replacing earlier ones."""
    out: dict = {}
    for r in records:
        if r.get("kind") not in ("sens", "point", "done"):
            continue
        p = out.setdefault((r["region"], r["mode"]),
                           {"sens": None, "points": {}, "done": None})
        if r["kind"] == "sens":
            p["sens"] = float(r["value"])
        elif r["kind"] == "point":
            p["points"][int(r["k"])] = float(r["t"])
        else:
            p["done"] = r
    return out


def grid_for(sensitivity: float) -> tuple:
    for above, grid in PROBE_GRIDS:
        if sensitivity > above:
            return grid
    raise ValueError(sensitivity)


def walk(grid, points: dict) -> list | None:
    """The ks a sweep over ``grid`` measures before the stop rule ends it;
    None where a point it needs was not stored."""
    ks, n_over = [], 0
    for k in grid:
        if k not in points:
            return None
        ks.append(k)
        if points[k] / points[grid[0]] > STOP_RATIO:
            n_over += 1
            if n_over >= STOP_CONSECUTIVE:
                break
        else:
            n_over = 0
    return ks


def drift_corrected(ts, drift) -> np.ndarray:
    """Divide the linear ramp from t(0) to the re-timed t(0) out of the
    series; drift under 2 % or outside 0.5-2 is left alone."""
    ts = np.asarray(ts, np.float64)
    if drift is None or len(ts) < 3 or not (0.5 < drift < 2.0) \
            or abs(drift - 1.0) <= 0.02:
        return ts
    ramp = 1.0 + (drift - 1.0) * np.arange(len(ts)) / (len(ts) - 1)
    return ts / ramp


def fit(ks, ts, tol: float = FIT_TOL) -> dict:
    """The three-phase model t(k) = max(t0, t0 + s (k - k1)) by least
    squares over knees at the measured ks and their midpoints (a tie goes
    to the larger knee); the threshold reading, the last k before the first
    point over (1 + tol) t(0); and the saturation onset k2, the first point
    within tol of the line through the last three."""
    k = np.asarray(ks, np.float64)
    t = np.asarray(ts, np.float64)
    knees = sorted(set(k.tolist()) | set(((k[:-1] + k[1:]) / 2).tolist()),
                   reverse=True)
    best = (float("inf"), 0.0, 0.0)           # (sse, k1, slope)
    for k1 in knees:
        flat = k <= k1
        t0 = t[flat].mean() if flat.any() else t[0]
        x, y = k[~flat] - k1, t[~flat] - t0
        s = max(float((x * y).sum() / (x * x).sum()), 0.0) \
            if (x * x).sum() else 0.0
        sse = float(((np.where(flat, t0, t0 + s * (k - k1)) - t) ** 2).sum())
        if sse < best[0]:
            best = (sse, float(k1), s)
    _, k1, slope = best
    over = np.flatnonzero(t > (1 + tol) * t[0])
    k1_thr = float(k[-1]) if not over.size else \
        (float(k[over[0] - 1]) if over[0] > 0 else 0.0)
    k2 = k1
    if len(k) >= 3 and slope > 0:
        s2 = float(np.polyfit(k[-3:], t[-3:], 1)[0])
        b2 = float(t[-3:].mean() - s2 * k[-3:].mean())
        on = np.abs(t - (s2 * k + b2)) <= tol * np.maximum(t, 1e-12)
        k2 = max(float(k[np.argmax(on)]) if on.any() else float(k[-1]), k1)
    return {"k1": k1, "k1_threshold": k1_thr, "k2": k2}


def classify(absorptions: dict, low: float = LOW, high: float = HIGH) -> str:
    """The paper's decision table (section 4.2, Table 3) over the modes'
    absorptions, first rule that holds."""
    slot = {}
    for name, aliases in SLOTS.items():
        slot[name] = next((absorptions[a] for a in aliases
                           if a in absorptions), None)
    fp, l1, mem = slot["fp"], slot["l1"], slot["mem"]
    known = [v for v in slot.values() if v is not None]
    icis = [v for m, v in absorptions.items() if m.startswith("ici")]
    if icis and min(icis) <= low and (not known or min(known) >= high / 2):
        return "ici"
    if fp is not None and fp <= low and (
            (l1 is not None and l1 >= max(high / 2, 3.0 * max(fp, 1.0)))
            or (mem is not None and mem >= high)):
        return "compute"
    if mem is not None and mem <= low and (fp is None or fp >= high) \
            and (l1 is None or l1 > low):
        return "bandwidth"
    if mem is not None and mem > low and (fp is None or fp >= high):
        return "latency"
    if known and max(known) <= low:
        return "overlap"
    if l1 is not None and l1 <= low and (fp is None or fp > low):
        return "l1"
    return "mixed"


def payload_verified(done: dict | None) -> bool:
    """The pair's payload check ran and held: every requested pattern
    survived, at the last nonzero k swept (at most ``CHECK_K_MAX``), and
    the kernel's main output stayed within the tolerance it was checked
    against."""
    if not done or not done.get("payload"):
        return False
    p = done["payload"]
    swept = [k for k in done["ks"] if k]
    want = min(swept[-1] if swept else 8, CHECK_K_MAX)
    return (p["expected"] == want and p["payload"] == p["expected"]
            and p.get("ref_err") is not None
            and p["ref_err"] <= p["ref_tol"])


def compare(records: list, report: dict, pairs: list) -> dict:
    """Mismatch counts between one campaign's report and the reference
    rebuilt from its store's raw records.

    ``report``: ``{region: {"label", "modes": {mode: {"ks", "k1",
    "k1_threshold", "k2"}}}}`` as the program reported it; ``pairs``: the
    (region, mode) pairs the plan asks for."""
    got = pairs_of(records)
    out = dict.fromkeys(MISMATCHES, 0)
    by_region: dict = {}
    off: set = set()               # regions whose verdict cannot be rebuilt
    for region, mode in pairs:
        raw = got.get((region, mode))
        rep = report.get(region, {}).get("modes", {}).get(mode)
        if raw is None or rep is None or raw["sens"] is None \
                or raw["done"] is None:
            out["pairs_unverified"] += 1
            out["pairs_off_grid"] += 1
            off.add(region)
            continue
        if not payload_verified(raw["done"]):
            out["pairs_unverified"] += 1
        ks = walk(grid_for(raw["sens"]), raw["points"])
        if ks is None or list(rep["ks"]) != ks \
                or list(raw["done"]["ks"]) != ks:
            out["pairs_off_grid"] += 1
            off.add(region)
            continue
        ts = drift_corrected([raw["points"][k] for k in ks],
                             raw["done"].get("drift"))
        want = fit(ks, ts)
        if any(rep[f] != want[f] for f in want):
            out["fits_off"] += 1
        by_region.setdefault(region, {})[mode] = want["k1"]
    for region, k1s in by_region.items():
        if region not in off and report[region]["label"] != classify(k1s):
            out["verdicts_off"] += 1
    return out
