"""The campaign reference on hand-computed cases, and beside the program's
own fit and classifier on random curves (the reference imports nothing of
the program; this test imports both)."""
import numpy as np
import pytest

from chipbench.reference import campaign as C


def test_the_probe_selects_its_grid():
    assert C.grid_for(2.5)[-1] == 24
    assert C.grid_for(1.5)[-1] == 64
    assert C.grid_for(1.0) == (0, 5, 10, 20, 30, 40, 60, 80, 120, 160, 240,
                               320)


def test_the_stop_rule_ends_after_two_saturated_points():
    grid = (0, 1, 2, 3, 4, 6)
    points = {0: 1.0, 1: 4.5, 2: 3.0, 3: 4.1, 4: 5.0, 6: 9.0}
    assert C.walk(grid, points) == [0, 1, 2, 3, 4]
    del points[4]
    assert C.walk(grid, points) is None


def test_drift_correction():
    np.testing.assert_allclose(C.drift_corrected([1.0, 1.05, 1.1], 1.1),
                               [1.0, 1.0, 1.0])
    assert list(C.drift_corrected([1.0, 2.0, 3.0], 1.01)) == [1.0, 2.0, 3.0]
    assert list(C.drift_corrected([1.0, 2.0, 3.0], None)) == [1.0, 2.0, 3.0]


def test_fit_of_a_hinge_and_of_a_flat_curve():
    ks = [0, 5, 10, 20, 30]
    hinge = [max(1.0, 1.0 + 0.1 * (k - 10)) for k in ks]
    assert C.fit(ks, hinge) == {"k1": 10.0, "k1_threshold": 10.0, "k2": 10.0}
    # a flat curve is absorbed everywhere it was looked at
    assert C.fit(ks, [1.0] * 5) == {"k1": 30.0, "k1_threshold": 30.0,
                                    "k2": 30.0}


@pytest.mark.parametrize("fp, vmem, label", [
    (0.0, 320.0, "compute"), (320.0, 2.0, "l1"), (2.0, 3.0, "overlap"),
    (10.0, 10.0, "mixed")])
def test_classify(fp, vmem, label):
    assert C.classify({"fp": fp, "vmem": vmem}) == label


def test_payload_verified():
    done = {"ks": [0, 5, 10, 20], "payload": {
        "expected": 16, "payload": 16, "ref_err": 1e-7, "ref_tol": 1e-4}}
    assert C.payload_verified(done)
    assert not C.payload_verified({**done, "payload": None})
    assert not C.payload_verified(
        {**done, "payload": {**done["payload"], "payload": 0}})
    assert not C.payload_verified(
        {**done, "payload": {**done["payload"], "ref_err": 1e-3}})
    assert not C.payload_verified({**done, "ks": [0, 5, 10]})


def records_and_report(rng):
    recs, report = [], {}
    for region in ("r0", "r1"):
        modes = {}
        for mode in ("fp", "vmem"):
            ks = list(C.grid_for(1.0))
            knee = float(rng.choice(ks))
            ts = [1e-3 * (1 + 0.01 * rng.random()
                          + 0.02 * max(0.0, k - knee)) for k in ks]
            ks = C.walk(ks, dict(zip(ks, ts)))
            ts = ts[:len(ks)]
            drift = 1.0 + 0.1 * rng.random()
            recs.append({"kind": "sens", "region": region, "mode": mode,
                         "value": 1.0})
            recs += [{"kind": "point", "region": region, "mode": mode,
                      "k": k, "t": t} for k, t in zip(ks, ts)]
            recs.append({"kind": "done", "region": region, "mode": mode,
                         "ks": ks, "drift": drift, "payload": {
                             "expected": 16, "payload": 16,
                             "ref_err": 0.0, "ref_tol": 1e-4}})
            modes[mode] = {"ks": ks, **C.fit(ks, C.drift_corrected(ts,
                                                                    drift))}
        report[region] = {"label": C.classify(
            {m: v["k1"] for m, v in modes.items()}), "modes": modes}
    return recs, report


PAIRS = [(r, m) for r in ("r0", "r1") for m in ("fp", "vmem")]


def test_compare_finds_nothing_in_a_sound_campaign():
    recs, report = records_and_report(np.random.default_rng(3))
    assert C.compare(recs, report, PAIRS) == dict.fromkeys(C.MISMATCHES, 0)


def test_compare_counts_each_mismatch():
    recs, report = records_and_report(np.random.default_rng(3))
    report["r1"]["label"] = "bandwidth"
    report["r0"]["modes"]["fp"]["k2"] += 1
    assert C.compare(recs, report, PAIRS) == {
        "pairs_unverified": 0, "pairs_off_grid": 0, "fits_off": 1,
        "verdicts_off": 1}
    # a pair cut short, and a pair with no done record: their regions'
    # verdicts cannot be rebuilt and count only as those pairs
    report["r0"]["modes"]["vmem"]["ks"] = report["r0"]["modes"]["vmem"][
        "ks"][:-1]
    recs = [r for r in recs if not (r["kind"] == "done" and
                                    (r["region"], r["mode"]) == ("r1", "fp"))]
    assert C.compare(recs, report, PAIRS) == {
        "pairs_unverified": 1, "pairs_off_grid": 2, "fits_off": 1,
        "verdicts_off": 0}


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_reference_agrees_with_the_program_on_random_curves(seed):
    from repro.core.absorption import assemble_curve, fit_three_phase
    from repro.core.classifier import classify

    rng = np.random.default_rng(seed)
    for _ in range(50):
        ks = list(C.grid_for(float(rng.choice([1.0, 1.5, 2.5]))))
        knee = float(rng.choice(ks))
        ts = [1e-3 * (1 + 0.04 * rng.random()
                      + rng.choice([0.005, 0.05]) * max(0.0, k - knee))
              for k in ks]
        drift = float(rng.choice([1.0, 1.01, 0.95, 1.2]))
        ours = C.fit(ks, C.drift_corrected(ts, drift))
        curve = assemble_curve("fp", ks, ts, drift=drift)
        theirs = fit_three_phase(curve.ks, curve.ts)
        assert ours == {"k1": theirs.k1, "k1_threshold": theirs.k1_threshold,
                        "k2": theirs.k2}
        ab = {"fp": ours["k1"], "vmem": float(rng.choice(ks))}
        assert C.classify(ab) == classify(ab).label
