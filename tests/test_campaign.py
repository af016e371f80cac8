"""Campaign engine + compile-once sweep path: trace-count guarantees,
static/runtime-k equivalence, store resume semantics, multi-store
fan-out/merge, store-backed DECAN."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Campaign, CampaignStore, CampaignStoreError,
                        Controller, DecanTarget, merge_stores, step_region,
                        worker_store)
from repro.core.absorption import DEFAULT_KS
from repro.core.controller import loop_region
from repro.core.loopnoise import make_loop_modes
from repro.core.noise import NoiseScale, make_modes

MODES = make_modes(NoiseScale(hbm_mib=4, chase_len=1 << 16, mxu_dim=32))

def _make_counting_region(name="tiny"):
    """A tiny region whose step counts Python traces — each jit compilation
    traces exactly once, so the counter counts compiled executables."""
    traces = {"n": 0}

    def step(x):
        traces["n"] += 1
        W = jnp.eye(64) * 0.5
        return jnp.tanh(x @ W) @ W

    X = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    return step_region(name, step, (X,), MODES), traces


# ---------------------------------------------------------------------------
# compile-once path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fp_add32", "mxu_fma128", "vmem_ld",
                                  "hbm_stream", "hbm_latency"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_runtime_k_matches_static(mode, k):
    """apply_rt(state, k) must emit the same patterns as apply(state, k):
    identical aux and identical new state, so both sweep paths measure the
    same injected work."""
    m = MODES[mode]
    state = m.make_state(jax.random.PRNGKey(0))
    aux_s, new_s = m.apply(state, k)
    aux_r, new_r = jax.jit(m.apply_rt)(state, jnp.int32(k))
    np.testing.assert_allclose(np.asarray(aux_s), np.asarray(aux_r),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(new_s), jax.tree.leaves(new_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["fp_add", "fp_fma", "l1_ld", "chase"])
def test_loop_emit_rt_matches_static(mode):
    m = make_loop_modes()[mode]
    nc = m.init(jax.random.PRNGKey(0))
    for k in (1, 5):
        s = m.emit(nc, k, jnp.int32(3))
        r = jax.jit(lambda c, kk: m.emit_rt(c, kk, jnp.int32(3)))(
            nc, jnp.int32(k))
        for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(r)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


def test_sweep_compiles_at_most_two_executables():
    """Acceptance: a DEFAULT_KS sweep on the compile-once path traces at most
    2 executables (the runtime-k one + the static payload check) instead of
    one per k."""
    region, traces = _make_counting_region()
    ctl = Controller(reps=2)
    res = ctl.run_mode(region, "fp_add32", ks=DEFAULT_KS)
    assert traces["n"] <= 2, f"{traces['n']} executables for one sweep"
    assert len(res.curve.ks) >= 3    # the sweep actually happened
    assert res.injection is not None  # payload was verified (static trace)


@pytest.mark.parametrize("call", ["probe_sensitivity", "run_mode"])
def test_controller_refuses_region_without_runtime_k_build(call):
    """Runtime k is the only way a sweep delivers k: a target without
    ``build_rt`` is refused by name, and nothing is built or measured."""
    from repro.core.controller import RegionTarget

    def never(*a):
        raise AssertionError("a refused region must not be built")

    target = RegionTarget(name="replay_only", build=never, args_for=never)
    ctl = Controller(reps=2)
    with pytest.raises(ValueError, match="'replay_only' has no runtime-k "
                                         "build; it can be replayed, not "
                                         "measured"):
        getattr(ctl, call)(target, "fp_add32")
    assert ctl._rt_cache == {}


def test_loop_region_build_rt_matches_static():
    from repro.bench.kernels import stream_region

    r = stream_region(n=1 << 14)
    out_s = r.build("fp_add", 4)(*r.args_for("fp_add", 4))
    out_rt = r.build_rt("fp_add")(jnp.int32(4), *r.args_for_rt("fp_add"))
    for a, b in zip(jax.tree.leaves(out_s), jax.tree.leaves(out_rt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# campaign store + resume
# ---------------------------------------------------------------------------

def test_campaign_resume_measures_nothing(tmp_path):
    """Acceptance: re-running a completed campaign performs ZERO new
    measurements and reproduces the same RegionReport classification."""
    store = str(tmp_path / "store.jsonl")
    region1, _ = _make_counting_region("resume_region")
    c1 = Campaign(store, Controller(reps=2))
    rep1 = c1.characterize(region1, ["fp_add32", "vmem_ld"])
    assert c1.stats.measured > 0

    region2, traces2 = _make_counting_region("resume_region")
    c2 = Campaign(store, Controller(reps=2))
    rep2 = c2.characterize(region2, ["fp_add32", "vmem_ld"])
    assert c2.stats.measured == 0
    assert traces2["n"] == 0                      # not even a compile
    assert rep2.bottleneck.label == rep1.bottleneck.label
    for m in rep1.results:
        assert rep2.results[m].curve.ks == rep1.results[m].curve.ks
        assert rep2.results[m].curve.ts == rep1.results[m].curve.ts
        assert rep2.results[m].fit.k1 == rep1.results[m].fit.k1
        if rep1.results[m].injection is not None:
            assert (rep2.results[m].injection.payload
                    == rep1.results[m].injection.payload)


def test_campaign_partial_store_resumes_missing_points(tmp_path):
    """An interrupted campaign (points stored, no 'done' marker) resumes at
    the missing ks instead of remeasuring the stored prefix."""
    store_path = str(tmp_path / "store.jsonl")
    region, _ = _make_counting_region("partial_region")
    # stop_ratio high: a wall-clock spike on a loaded container must not
    # early-stop either sweep (the point-count asserts need the full ks)
    ctl = Controller(reps=2, verify_payload=False, stop_ratio=100.0)

    c1 = Campaign(store_path, ctl)
    full = c1.sweep_mode(region, "fp_add32")
    n_points = len(full.curve.ks)

    # rebuild a truncated store: sensitivity + the first two points only
    trunc = str(tmp_path / "trunc.jsonl")
    st = CampaignStore(trunc)
    st.append({"kind": "sens", "region": "partial_region",
               "mode": "fp_add32", "value": c1.store.sens[
                   ("partial_region", "fp_add32")]})
    for k in full.curve.ks[:2]:
        st.append({"kind": "point", "region": "partial_region",
                   "mode": "fp_add32", "k": k,
                   "t": c1.store.stored_ts("partial_region", "fp_add32")[k]})
    st.close()

    region2, _ = _make_counting_region("partial_region")
    c2 = Campaign(trunc, ctl)
    res = c2.sweep_mode(region2, "fp_add32")
    assert c2.stats.cached == 2                  # stored prefix replayed
    assert c2.stats.measured == n_points - 2     # only the tail measured
    assert res.curve.ks == full.curve.ks
    assert c2.store.is_done("partial_region", "fp_add32")


def test_campaign_settings_mismatch_discards_store(tmp_path):
    """A store measured under different settings (reps) must not be
    spliced into a new curve: the pair is discarded and remeasured."""
    store = str(tmp_path / "s.jsonl")
    region1, _ = _make_counting_region("meta_region")
    c1 = Campaign(store, Controller(reps=2, verify_payload=False))
    c1.sweep_mode(region1, "fp_add32")

    region2, _ = _make_counting_region("meta_region")
    c2 = Campaign(store, Controller(reps=3, verify_payload=False))
    c2.sweep_mode(region2, "fp_add32")
    assert c2.stats.measured > 0          # stored sweep was NOT replayed
    assert c2.stats.cached == 0

    # same settings again -> replay, nothing measured
    region3, _ = _make_counting_region("meta_region")
    c3 = Campaign(store, Controller(reps=3, verify_payload=False))
    c3.sweep_mode(region3, "fp_add32")
    assert c3.stats.measured == 0


def test_campaign_replays_pairs_stored_with_compile_once_false(tmp_path):
    """Stores written by the removed trace-per-k sweep carry
    ``"compile_once": false`` in their meta records; under the same reps
    they replay without a measurement, and the store keeps its bytes."""
    store = str(tmp_path / "s.jsonl")
    region, traces = _make_counting_region("old_store")
    c1 = Campaign(store, Controller(reps=2, verify_payload=False,
                                    stop_ratio=100.0))
    first = c1.sweep_mode(region, "fp_add32")
    c1.store.close()
    with open(store) as f:
        recs = [json.loads(line) for line in f]
    metas = [r for r in recs if r["kind"] == "meta"]
    assert [m["compile_once"] for m in metas] == [True]
    for r in metas:
        r["compile_once"] = False
    with open(store, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    before = open(store, "rb").read()

    traces["n"] = 0
    c2 = Campaign(store, Controller(reps=2, verify_payload=False))
    again = c2.sweep_mode(region, "fp_add32")
    c2.store.close()
    assert c2.stats.measured == 0 and c2.stats.cached == len(first.curve.ks)
    assert again.curve.ks == first.curve.ks
    assert again.curve.ts == first.curve.ts
    assert traces["n"] == 0                      # nothing was built
    assert open(store, "rb").read() == before    # no meta rewritten


def test_campaign_worker_pool(tmp_path):
    region, _ = _make_counting_region("pool_region")
    c = Campaign(str(tmp_path / "s.jsonl"),
                 Controller(reps=2, verify_payload=False), workers=3)
    reps = c.run([region], ["fp_add32", "vmem_ld", "hbm_stream"])
    assert set(reps["pool_region"].results) == {"fp_add32", "vmem_ld",
                                                "hbm_stream"}
    assert c.stats.measured > 0


def test_store_survives_reload(tmp_path):
    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 4, "t": 0.5})
    st.append({"kind": "sens", "region": "r", "mode": "m", "value": 1.5})
    st.close()
    st2 = CampaignStore(path)
    assert st2.stored_ts("r", "m") == {4: 0.5}
    assert st2.sens[("r", "m")] == 1.5
    st2.close()


# ---------------------------------------------------------------------------
# div-zero hardening (satellite)
# ---------------------------------------------------------------------------

def test_zero_baseline_clamped_with_warning():
    from repro.core.absorption import AbsorptionCurve

    curve = AbsorptionCurve(mode="m", ks=[0, 1], ts=[0.0, 1.0])
    with pytest.warns(RuntimeWarning, match="timer resolution"):
        r = curve.ratios()
    assert np.all(np.isfinite(r))


def test_probe_sensitivity_zero_baseline(monkeypatch):
    import repro.core.controller as ctl_mod

    region, _ = _make_counting_region("zero_t0")
    monkeypatch.setattr(ctl_mod, "measure", lambda *a, **k: 0.0)
    c = Controller(reps=2)
    with pytest.warns(RuntimeWarning, match="timer resolution"):
        s = c.probe_sensitivity(region, "fp_add32")
    assert np.isfinite(s)


# ---------------------------------------------------------------------------
# truncated / corrupt stores (the "loses at most one point" guarantee)
# ---------------------------------------------------------------------------

def _cut_final_record(path, src, nbytes=9):
    data = open(src, "rb").read()
    assert data.endswith(b"\n")
    with open(path, "wb") as f:
        f.write(data[:-nbytes])     # torn mid-append: partial last record


def test_truncated_final_line_resumes_with_one_point_lost(tmp_path):
    """A process killed mid-append leaves a partial last record; reopening
    the store must warn, drop ONLY that record, and resume."""
    full = str(tmp_path / "full.jsonl")
    region, _ = _make_counting_region("trunc_region")
    ctl = Controller(reps=2, verify_payload=False)
    res = Campaign(full, ctl).sweep_mode(region, "fp_add32")
    n_points = len(res.curve.ks)

    # cut mid-"done": the sweep resumes from its points, remeasures nothing
    trunc = str(tmp_path / "t1.jsonl")
    _cut_final_record(trunc, full)
    region2, traces2 = _make_counting_region("trunc_region")
    c2 = Campaign(trunc, ctl)
    assert not c2.store.is_done("trunc_region", "fp_add32")
    res2 = c2.sweep_mode(region2, "fp_add32")
    assert c2.stats.measured == 0 and c2.stats.cached == n_points
    assert res2.curve.ks == res.curve.ks

    # cut mid-"point" (strip the done line first): exactly one k remeasured
    lines = open(full).read().strip().split("\n")
    assert json.loads(lines[-1])["kind"] == "done"
    trunc2 = str(tmp_path / "t2.jsonl")
    with open(trunc2, "w") as f:
        f.write("\n".join(lines[:-1]) + "\n")
    _cut_final_record(trunc2, trunc2)
    region3, _ = _make_counting_region("trunc_region")
    c3 = Campaign(trunc2, ctl)
    c3.sweep_mode(region3, "fp_add32")
    assert c3.stats.measured == 1                 # the torn point only
    assert c3.stats.cached == n_points - 1
    # and the store is fully healed: a fresh campaign replays everything
    region4, _ = _make_counting_region("trunc_region")
    c4 = Campaign(trunc2, ctl)
    c4.sweep_mode(region4, "fp_add32")
    assert c4.stats.measured == 0


def test_corruption_before_final_record_hard_fails(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    st = CampaignStore(path)
    st.append({"kind": "sens", "region": "r", "mode": "m", "value": 1.5})
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 0.5})
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 2, "t": 0.6})
    st.close()
    lines = open(path).read().strip().split("\n")
    with open(path, "w") as f:   # damage a MIDDLE record, keep the tail
        f.write(lines[0] + "\n" + lines[1][:-4] + "\n" + lines[2] + "\n")
    with pytest.raises(CampaignStoreError, match="corrupt record"):
        CampaignStore(path)


# ---------------------------------------------------------------------------
# multi-store fan-out + merge (acceptance: split across >=2 stores, merged,
# replays with ZERO new measurements and identical classification)
# ---------------------------------------------------------------------------

def _fake_measure(fn, args=(), **kw):
    """Deterministic synthetic wall-clock: t(k) has a knee at k=6. The
    fan-out/merge tests compare two independently-run campaigns, so timing
    must be a pure function of k (args[0] on the runtime-k path)."""
    k = int(args[0]) if args else 0
    return 1e-3 * (1.0 + max(0, k - 6) * 0.05)


@pytest.fixture
def fake_measure(monkeypatch):
    import repro.core.campaign as campaign_mod
    import repro.core.controller as ctl_mod

    monkeypatch.setattr(campaign_mod, "measure", _fake_measure)
    monkeypatch.setattr(ctl_mod, "measure", _fake_measure)


def test_fanout_merge_replay_matches_single_store(tmp_path, fake_measure):
    """Acceptance: a campaign split across 2 worker stores, merged with
    merge_stores(), replays with ZERO new measurements, byte-identical
    ModeResults, and the same classification as the single-store run."""
    modes = ["fp_add32", "vmem_ld", "hbm_stream"]

    def fresh(name="fan_region"):
        region, traces = _make_counting_region(name)
        return region, traces, Controller(reps=2, verify_payload=False)

    # reference: one store, one process
    region, _, ctl = fresh()
    single = Campaign(str(tmp_path / "single.jsonl"), ctl)
    ref = single.characterize(region, modes)

    # fan-out: every (region, mode) pair measured by exactly one worker
    base = str(tmp_path / "fan.jsonl")
    worker_results = {}
    for i in (0, 1):
        region, _, ctl = fresh()
        c = Campaign(worker_store(base, i, 2), ctl)
        res = c.measure_shard([region], modes, index=i, count=2)
        assert c.stats.cached == 0 and c.stats.measured > 0
        assert not set(res) & set(worker_results)    # disjoint slices
        worker_results.update(res)
    assert set(worker_results) == {("fan_region", m) for m in modes}

    stats = merge_stores(base, [worker_store(base, i, 2) for i in (0, 1)])
    assert not stats.conflicts

    region, traces, ctl = fresh()
    merged = Campaign(base, ctl)
    rep = merged.characterize(region, modes)
    assert merged.stats.measured == 0               # ZERO new measurements
    assert traces["n"] == 0                         # not even a compile
    for m in modes:                                 # byte-identical replay
        assert rep.results[m] == worker_results[("fan_region", m)]
        assert rep.results[m] == ref.results[m]     # == single-store run
    assert rep.bottleneck.label == ref.bottleneck.label


def test_merge_is_idempotent_and_order_independent(tmp_path, fake_measure):
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    for path, name in ((a, "rA"), (b, "rB")):     # disjoint key sets
        region, _, = _make_counting_region(name)[:2]
        Campaign(path, Controller(reps=2, verify_payload=False)) \
            .sweep_mode(region, "fp_add32")
    ab = str(tmp_path / "ab.jsonl")
    ba = str(tmp_path / "ba.jsonl")
    merge_stores(ab, [a, b])
    merge_stores(ba, [b, a])
    assert open(ab).read() == open(ba).read()     # order-independent
    again = str(tmp_path / "again.jsonl")
    merge_stores(again, [ab])
    assert open(again).read() == open(ab).read()  # idempotent
    merge_stores(ab, [ab, ba])                    # dest may be a source
    assert open(again).read() == open(ab).read()


def test_merge_meta_conflict_later_store_wins(tmp_path):
    """The same pair measured under different settings in two stores must
    not splice: the later source supersedes the earlier pair entirely."""
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, reps, t in ((a, 2, 0.5), (b, 3, 0.9)):
        st = CampaignStore(path)
        st.append({"kind": "meta", "region": "r", "mode": "m",
                   "reps": reps, "compile_once": True})
        st.append({"kind": "point", "region": "r", "mode": "m",
                   "k": 0, "t": t})
        st.append({"kind": "point", "region": "r", "mode": "m",
                   "k": 4 if reps == 2 else 8, "t": t})
        st.close()
    out = str(tmp_path / "m.jsonl")
    stats = merge_stores(out, [a, b])
    assert ("r", "m") in stats.conflicts
    st = CampaignStore(out)
    st.close()
    assert st.meta[("r", "m")]["reps"] == 3
    assert st.stored_ts("r", "m") == {0: 0.9, 8: 0.9}   # a's points dropped


def test_merge_stores_cleans_tmp_on_corrupt_source(tmp_path):
    """Satellite regression: a source raising CampaignStoreError mid-merge
    must not leave ``dest + '.merge-tmp'`` behind, and must not touch an
    existing dest."""
    good = str(tmp_path / "good.jsonl")
    st = CampaignStore(good)
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 0.5})
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 2, "t": 0.6})
    st.close()
    bad = str(tmp_path / "bad.jsonl")
    lines = open(good).read().strip().split("\n")
    with open(bad, "w") as f:   # corrupt MIDDLE record: loader hard-fails
        f.write(lines[0][:-4] + "\n" + lines[1] + "\n")
    dest = str(tmp_path / "dest.jsonl")
    with open(dest, "w") as f:
        f.write(lines[0] + "\n")
    before = open(dest).read()
    with pytest.raises(CampaignStoreError):
        merge_stores(dest, [good, bad])
    assert not glob.glob(dest + ".merge-tmp*")
    assert open(dest).read() == before          # dest untouched by the abort
    # and a successful merge leaves no tmp either
    merge_stores(dest, [good])
    assert not glob.glob(dest + ".merge-tmp*")


def test_concurrent_merges_use_distinct_tmp_names(tmp_path):
    """Regression: two merges into the SAME dest used to share the literal
    ``dest + '.merge-tmp'`` scratch name, so concurrent merges could rename
    each other's half-written tmp into place. The tmp name is now unique
    per call, and every call still cleans its own tmp up."""
    import threading

    from repro.core.campaign import _MERGE_TMP_COUNT

    srcs = []
    for i in range(4):
        p = str(tmp_path / f"s{i}.jsonl")
        st = CampaignStore(p)
        for k in range(32):
            st.append({"kind": "point", "region": f"r{i}", "mode": "m",
                       "k": k, "t": 0.1 * (k + 1)})
        st.close()
        srcs.append(p)
    dest = str(tmp_path / "dest.jsonl")
    c0 = next(_MERGE_TMP_COUNT)
    errs = []

    def one():
        try:
            merge_stores(dest, srcs)
        except Exception as e:          # pragma: no cover - the regression
            errs.append(e)

    threads = [threading.Thread(target=one) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert next(_MERGE_TMP_COUNT) >= c0 + 5     # each call drew a fresh name
    assert not glob.glob(dest + ".merge-tmp*")  # nobody leaked a tmp
    st = CampaignStore(dest, readonly=True)
    st.close()
    assert len(st.points) == 4 and all(len(v) == 32
                                       for v in st.points.values())


def _meta(reps, **kw):
    return {"kind": "meta", "region": "r", "mode": "m", "reps": reps,
            "compile_once": True, **kw}


_AUDIT = {"kind": "audit", "region": "r", "mode": "m", "verdict": "dead",
          "survival": 0.0, "corruption": None, "predicted": "fp",
          "target": "fp", "agrees": None, "resources": {}, "k_lo": 1,
          "k_hi": 8, "detail": "stale"}


def test_meta_conflict_drops_stale_audit_in_store_replay(tmp_path):
    """Regression: a settings change discarded the pair's points/sens/done
    but KEPT its audit record, so stale static-audit evidence (measured
    under the old settings) annotated the re-measured pair. preds carry
    their settings inline and must survive."""
    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    st.append(_meta(2))
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 1.0})
    st.append(dict(_AUDIT))
    st.append({"kind": "pred", "region": "r", "mode": "m", "ks": [0],
               "ts": [1.0], "fit": {}, "hw": {}, "terms": {}, "alpha": 1.0,
               "tol": 0.05, "k_max": 8})
    st.append(_meta(3))                         # settings conflict
    st.close()
    assert ("r", "m") not in st.points
    assert ("r", "m") not in st.audits          # the stale audit is gone
    assert ("r", "m") in st.preds               # preds supersede on their own
    # and the same discard happens on a cold replay of the file
    st2 = CampaignStore(path, readonly=True)
    st2.close()
    assert ("r", "m") not in st2.audits and ("r", "m") in st2.preds


def test_merge_meta_conflict_drops_stale_audit(tmp_path):
    """The merge view applies the same rule across stores: the earlier
    source's audit must not survive a meta conflict with a later source."""
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    st = CampaignStore(a)
    st.append(_meta(2))
    st.append(dict(_AUDIT))
    st.close()
    st = CampaignStore(b)
    st.append(_meta(3))
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 2.0})
    st.close()
    out = str(tmp_path / "m.jsonl")
    stats = merge_stores(out, [a, b])
    assert ("r", "m") in stats.conflicts
    merged = CampaignStore(out, readonly=True)
    merged.close()
    assert ("r", "m") not in merged.audits
    assert merged.stored_ts("r", "m") == {0: 2.0}
    assert "audit" not in open(out).read()      # dropped from the bytes too


def test_inspect_reports_grid_completeness(tmp_path, capsys):
    """Satellite: ``inspect`` reports per-(region, mode) points present vs
    expected, flags missing ks, and summarizes grid completeness."""
    from repro.core.campaign import _cli

    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    st.append({"kind": "point", "region": "rA", "mode": "m", "k": 0, "t": 1.0})
    st.append({"kind": "point", "region": "rA", "mode": "m", "k": 2, "t": 1.0})
    st.append({"kind": "done", "region": "rA", "mode": "m", "ks": [0, 2, 4],
               "drift": None, "stopped_early": False, "payload": None})
    st.append({"kind": "point", "region": "rB", "mode": "m", "k": 0, "t": 1.0})
    st.close()
    assert _cli(["inspect", path]) == 0
    out = capsys.readouterr().out
    assert "measured rA/m: 2/3 point(s), done, MISSING ks [4]" in out
    assert "measured rB/m: 1 point(s), in progress" in out
    assert "grid: 0/2 measured pair(s) complete" in out


def test_merge_cli_round_trip(tmp_path, capsys):
    from repro.core.campaign import _cli

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, region in ((a, "r1"), (b, "r2")):
        st = CampaignStore(path)
        st.append({"kind": "point", "region": region, "mode": "m",
                   "k": 0, "t": 0.25})
        st.close()
    out = str(tmp_path / "merged.jsonl")
    assert _cli(["merge", out, a, b]) == 0
    st = CampaignStore(out)
    st.close()
    assert st.stored_ts("r1", "m") == {0: 0.25}
    assert st.stored_ts("r2", "m") == {0: 0.25}
    assert _cli(["inspect", out]) == 0
    assert "r1/m" in capsys.readouterr().out


def test_measure_shard_covers_grid_exactly_once(tmp_path, fake_measure):
    regions = [_make_counting_region(f"g{i}")[0] for i in range(2)]
    modes = ["fp_add32", "vmem_ld"]
    seen = []
    for i in range(3):
        c = Campaign(str(tmp_path / f"w{i}.jsonl"),
                     Controller(reps=2, verify_payload=False))
        seen += list(c.measure_shard(regions, modes, index=i, count=3))
    assert sorted(seen) == sorted((r.name, m) for r in regions for m in modes)
    with pytest.raises(ValueError, match="shard index"):
        Campaign(str(tmp_path / "w9.jsonl")).measure_shard(
            regions, modes, index=3, count=3)


# ---------------------------------------------------------------------------
# store-backed DECAN + the compile-once noise arm of a DecanTarget
# ---------------------------------------------------------------------------

def _counting_decan(name="dec"):
    traces = {"n": 0}
    X = jax.random.normal(jax.random.PRNGKey(0), (16, 64))

    def kernel(fp, ls, noise=None, k=0):
        def fn(x, *nc):
            traces["n"] += 1
            out = jnp.float32(0)
            if fp:
                out = out + jnp.sum(jnp.tanh(x) * 0.5)
            if ls:
                out = out + jnp.sum(x[::4])
            if noise is not None:
                c = jax.lax.fori_loop(
                    0, 8, lambda i, c: noise.emit(c, k, i), nc[0])
                return out, noise.finalize(c)
            return out
        return jax.jit(fn)

    target = DecanTarget(name, kernel, lambda: (X,),
                         build_noisy=lambda noise, k:
                             kernel(True, True, noise, k))
    return target, traces


def test_run_decan_replays_from_store(tmp_path):
    target, _ = _counting_decan()
    c1 = Campaign(str(tmp_path / "d.jsonl"),
                  Controller(reps=2, verify_payload=False))
    r1 = c1.run_decan(target)
    assert c1.stats.measured == 3 and c1.stats.cached == 0

    target2, traces2 = _counting_decan()
    c2 = Campaign(str(tmp_path / "d.jsonl"),
                  Controller(reps=2, verify_payload=False))
    r2 = c2.run_decan(target2)
    assert c2.stats.measured == 0 and c2.stats.cached == 3
    assert traces2["n"] == 0                       # replay never compiles
    assert r2 == r1                                # byte-identical timings

    # different settings supersede instead of replaying
    target3, _ = _counting_decan()
    c3 = Campaign(str(tmp_path / "d.jsonl"),
                  Controller(reps=3, verify_payload=False))
    c3.run_decan(target3)
    assert c3.stats.measured == 3


def test_decan_region_noise_arm_compiles_at_most_two(tmp_path):
    """Acceptance (table3 pattern): the noise arm of a DecanTarget sweeps a
    whole (scenario, mode) grid point on ≤2 executables — including the
    sensitivity probe — instead of one per k."""
    target, traces = _counting_decan("dec_rt")
    region = target.region()
    camp = Campaign(str(tmp_path / "d.jsonl"),
                    Controller(reps=2, verify_payload=False))
    res = camp.sweep_mode(region, "fp_add")
    assert traces["n"] <= 2, f"{traces['n']} executables for one sweep"
    assert len(res.curve.ks) >= 3

    # second mode: its own runtime-k executable, still ≤2 more
    camp.sweep_mode(region, "l1_ld")
    assert traces["n"] <= 4


def test_decan_region_requires_build_noisy():
    target = DecanTarget("bare", lambda fp, ls: (lambda: 0), lambda: ())
    with pytest.raises(ValueError, match="build_noisy"):
        target.region()


def test_campaign_sweep_with_sensitivity_compiles_at_most_two():
    """The memoized runtime-k callable: sensitivity probe + sweep + drift
    check share ONE executable (payload verification adds the second)."""
    region, traces = _make_counting_region("memo_region")
    camp = Campaign(CampaignStore(os.devnull), Controller(reps=2))
    camp.sweep_mode(region, "fp_add32")
    assert traces["n"] <= 2, f"{traces['n']} executables incl. sensitivity"


def test_final_record_missing_newline_is_healed(tmp_path):
    """A torn append that flushed the whole record but not its '\\n' must
    not glue the next append onto the same line: the loader heals the
    terminator and keeps the record (zero points lost)."""
    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 0.5})
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 2, "t": 0.6})
    st.close()
    with open(path, "r+b") as f:        # strip ONLY the final newline
        f.truncate(os.path.getsize(path) - 1)

    st2 = CampaignStore(path)
    assert st2.stored_ts("r", "m") == {0: 0.5, 2: 0.6}   # nothing lost
    st2.append({"kind": "point", "region": "r", "mode": "m", "k": 4, "t": 0.7})
    st2.close()
    st3 = CampaignStore(path)            # and the file stayed line-per-record
    st3.close()
    assert st3.stored_ts("r", "m") == {0: 0.5, 2: 0.6, 4: 0.7}


def test_readonly_store_neither_creates_nor_heals(tmp_path):
    missing = str(tmp_path / "nope.jsonl")
    with pytest.raises(FileNotFoundError):
        CampaignStore(missing, readonly=True)
    assert not os.path.exists(missing)   # inspection must not create stores

    path = str(tmp_path / "s.jsonl")
    st = CampaignStore(path)
    st.append({"kind": "point", "region": "r", "mode": "m", "k": 0, "t": 0.5})
    st.close()
    with open(path, "ab") as f:          # torn tail
        f.write(b'{"kind": "poi')
    before = open(path, "rb").read()
    ro = CampaignStore(path, readonly=True)
    ro.close()
    assert ro.stored_ts("r", "m") == {0: 0.5}
    assert open(path, "rb").read() == before     # readonly: file untouched
    with pytest.raises(RuntimeError, match="readonly"):
        ro.append({"kind": "sens", "region": "r", "mode": "m", "value": 1.0})
    CampaignStore(path).close()                  # writable open heals it
    assert open(path, "rb").read() != before


def test_rt_cache_is_per_target_not_per_name():
    """Two same-named targets on one Controller must not share a runtime-k
    executable: the cache keys on target identity."""
    ctl = Controller(reps=2, verify_payload=False)
    region_a, traces_a = _make_counting_region("same_name")
    region_b, traces_b = _make_counting_region("same_name")
    fn_a = ctl._rt_fn(region_a, "fp_add32")
    fn_b = ctl._rt_fn(region_b, "fp_add32")
    assert fn_a is ctl._rt_fn(region_a, "fp_add32")   # memoized per target
    assert fn_a is not fn_b                           # not shared by name
    fn_b(jnp.int32(1), *region_b.args_for_rt("fp_add32"))
    assert traces_b["n"] == 1 and traces_a["n"] == 0  # b's fn runs b's step
