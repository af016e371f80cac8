"""The Pallas layer on the Controller/Campaign spine: compile-count
guarantees (one executable per (kernel, mode) sweep, payload check
included, shared by same-shaped regions), oracle payload verification,
campaign persist/replay with zero new measurements, and multi-size families
sharing one store namespace."""
import contextlib
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Campaign, Controller
from repro.kernels import region as region_mod
from repro.kernels.region import (CHECK_K_MAX, KERNEL_MODES, family_names,
                                  oracle_rtol, pallas_family, pallas_region,
                                  validate_size)
from repro.kernels.spmv_ell.ref import (fp_noise_ell_ref, make_band_ell,
                                        vmem_noise_ell_ref)


def _counting_region(kernel, **sizes):
    traces = {"n": 0}
    region = pallas_region(
        kernel, backend="interpret",
        trace_hook=lambda: traces.__setitem__("n", traces["n"] + 1), **sizes)
    return region, traces


# small interpret-mode shapes so sweeps stay fast
SIZES = {
    "matmul": {"n": 128},
    "spmxv": {"n": 256},
    "attention": {"seq": 128, "heads": 2, "kv_heads": 2, "bq": 64, "bk": 64},
    "probe": {"n_steps": 8},
}


@pytest.mark.parametrize("kernel", sorted(KERNEL_MODES))
def test_pallas_sweep_compiles_at_most_two_per_mode(kernel):
    """Acceptance: a full k-sweep over a Pallas region, its sensitivity
    probe and its payload check build exactly ONE executable per (kernel,
    mode) — the runtime-k build the sweep times also counts the patterns —
    inside the ≤2-executables guarantee of the loop/graph layers."""
    region, traces = _counting_region(kernel, **SIZES[kernel])
    ctl = Controller(reps=2)
    before = 0
    for mode in KERNEL_MODES[kernel]:
        ctl.probe_sensitivity(region, mode)
        res = ctl.run_mode(region, mode, ks=(0, 1, 2, 4, 8, 16))
        built = traces["n"] - before
        before = traces["n"]
        assert built == 1, f"{kernel}/{mode}: {built} executables for a sweep"
        assert len(res.curve.ks) >= 3
        assert res.injection is not None          # oracle payload check ran
        assert res.injection.payload == res.injection.expected > 0


@pytest.mark.parametrize("kernel", sorted(KERNEL_MODES))
def test_pallas_payload_check_after_the_sweep_traces_nothing(kernel):
    """The payload check runs the executable the sweep timed: after
    ``run_mode`` it traces nothing new, at a k below and above the cap."""
    region, traces = _counting_region(kernel, **SIZES[kernel])
    ctl = Controller(reps=2, verify_payload=False, stop_ratio=100.0)
    mode = KERNEL_MODES[kernel][0]
    ctl.run_mode(region, mode, ks=(0, 2, 4))
    assert traces["n"] == 1
    for k in (4, CHECK_K_MAX + 4):
        assert region.payload_check(mode, k).ok()
    assert traces["n"] == 1


def test_pallas_payload_check_oracle():
    """The Pallas payload check verifies the nacc oracle on a static trace:
    full survival for every supported mode, reported per the §2.3 schema."""
    region, _ = _counting_region("matmul", n=128)
    for mode in KERNEL_MODES["matmul"]:
        rep = region.payload_check(mode, 6)
        assert rep.expected == rep.payload == 6
        assert rep.overhead == 0 and rep.survival_fraction == 1.0
        assert rep.ok()


@pytest.mark.parametrize("mode", sorted(KERNEL_MODES["spmxv"]))
def test_pallas_payload_check_oracle_spmxv(mode):
    """The SPMXV payload check holds its kernel to the host nacc oracle."""
    region, _ = _counting_region("spmxv", n=256)
    rep = region.payload_check(mode, 6)
    assert rep.expected == rep.payload == 6
    assert rep.ok()


@pytest.mark.parametrize("mode", sorted(KERNEL_MODES["spmxv"]))
@pytest.mark.parametrize("k_off", [-1, 1])
def test_spmxv_oracle_separates_a_missing_pattern(mode, k_off):
    """At the benchmark's 2^15 rows and k = 16 the payload check's
    tolerance still tells the k = 16 oracle from one a pattern off."""
    vals, _ = make_band_ell(2 ** 15, 16, 1.0, seed=0)
    oracle = {"fp": fp_noise_ell_ref, "vmem": vmem_noise_ell_ref}[mode]
    want = oracle(vals, 16, 128)
    assert not np.allclose(oracle(vals, 16 + k_off, 128), want,
                           rtol=oracle_rtol(16 * 256), atol=1e-5)


def _one_pattern_short(monkeypatch, kernel):
    """Make every ``kernel`` region built from here on run one noise pattern
    fewer than its runtime-k callable is asked for: a dead payload."""
    make_spec = region_mod._SPECS[kernel]

    def short_spec(interpret, **sizes):
        spec = make_spec(interpret, **sizes)
        rt_kernel = spec.rt_kernel

        def short(k, *args, **static):
            return rt_kernel(jnp.maximum(k - 1, 0), *args, **static)

        return dataclasses.replace(spec, rt_kernel=short)

    monkeypatch.setitem(region_mod._SPECS, kernel, short_spec)


@pytest.mark.parametrize("mode", sorted(KERNEL_MODES["spmxv"]))
@pytest.mark.parametrize("k", [6, CHECK_K_MAX + 4])
def test_runtime_k_payload_check_catches_a_dead_pattern(monkeypatch, mode, k):
    """The check counts patterns on the runtime-k build: a build one pattern
    short fails it, the unaltered build passes at min(k, CHECK_K_MAX)."""
    region, _ = _counting_region("spmxv", n=256)
    rep = region.payload_check(mode, k)
    assert rep.payload == rep.expected == min(k, CHECK_K_MAX)
    assert rep.ok()

    _one_pattern_short(monkeypatch, "spmxv")
    short, _ = _counting_region("spmxv", n=256)
    assert not short.payload_check(mode, k).ok()


def test_pallas_region_rejects_unknown_mode():
    region, _ = _counting_region("spmxv", n=256)
    with pytest.raises(ValueError, match="supports noise modes"):
        region.build("mxu", 2)       # spmv has no noise operand -> no mxu
    with pytest.raises(ValueError, match="unknown pallas kernel"):
        pallas_region("nope")


def test_pallas_campaign_replays_with_zero_measurements(tmp_path):
    """Acceptance: a completed Pallas campaign replays from its store with
    ZERO new measurements, zero compiles, and identical classification."""
    store = str(tmp_path / "pallas.jsonl")
    modes = list(KERNEL_MODES["spmxv"])

    region1, _ = _counting_region("spmxv", n=256)
    c1 = Campaign(store, Controller(reps=2))
    rep1 = c1.characterize(region1, modes)
    assert c1.stats.measured > 0

    region2, traces2 = _counting_region("spmxv", n=256)
    c2 = Campaign(store, Controller(reps=2))
    rep2 = c2.characterize(region2, modes)
    assert c2.stats.measured == 0
    assert traces2["n"] == 0                      # not even a compile
    assert rep2.bottleneck.label == rep1.bottleneck.label
    for m in modes:
        assert rep2.results[m].curve.ks == rep1.results[m].curve.ks
        assert rep2.results[m].curve.ts == rep1.results[m].curve.ts
        assert rep2.results[m].injection.payload \
            == rep1.results[m].injection.payload


def test_pallas_region_clean_build_is_noise_free():
    region, _ = _counting_region("matmul", n=128)
    out, nacc = region.build("", 0)(*region.args_for("", 0))
    assert out.shape == (128, 128)
    np.testing.assert_array_equal(np.asarray(nacc), 0.0)


def test_pallas_family_spans_sizes_and_q_grid():
    """One family call yields one RegionTarget per size (× q for spmxv),
    each with a distinct name — the store-namespace contract."""
    fam = pallas_family("probe", [8, 16], backend="interpret")
    assert [r.name for r in fam] == ["pallas_probe_s8", "pallas_probe_s16"]
    fam = pallas_family("spmxv", [256], qs=[0.0, 1.0], backend="interpret")
    assert [r.name for r in fam] == ["pallas_spmxv_n256_L16_q0",
                                     "pallas_spmxv_n256_L16_q1"]
    with pytest.raises(ValueError, match="spmxv"):
        pallas_family("matmul", [128], qs=[0.0], backend="interpret")
    with pytest.raises(ValueError, match="multiple"):
        pallas_family("matmul", [129], backend="interpret")
    with pytest.raises(ValueError, match="collide"):
        pallas_family("probe", [8, 8], backend="interpret")
    with pytest.raises(ValueError, match="unknown pallas kernel"):
        validate_size("nope", 8)


@pytest.mark.parametrize("kernel,sizes,qs,extra", [
    ("matmul", [128, 256], None, {}),
    ("spmxv", [256], [0.0, 0.25, 1.0], {"nnz_per_row": 8}),
    ("attention", [64, 128], None, {"heads": 4}),
    ("probe", [8, 64], None, {}),
])
def test_family_names_agree_with_built_regions(kernel, sizes, qs, extra):
    """``family_names`` (the cheap, build-nothing grid query) must produce
    exactly the names ``pallas_family`` builds — including every default the
    namers duplicate from the spec builders' signatures."""
    names = family_names(kernel, sizes, qs=qs, **extra)
    built = pallas_family(kernel, sizes, qs=qs, backend="interpret", **extra)
    assert names == [r.name for r in built]


def test_family_rejects_unknown_spec_params():
    with pytest.raises(ValueError, match="does not accept"):
        pallas_family("matmul", [128], nnz_per_row=8, backend="interpret")
    with pytest.raises(ValueError, match="does not accept"):
        family_names("probe", [8], causal=True)


def test_pallas_family_shares_one_campaign_store(tmp_path, monkeypatch):
    """Acceptance (ROADMAP): a single campaign store holds a kernel's whole
    size grid and replays every member with zero new measurements."""
    monkeypatch.setenv("REPRO_SYNTH_MEASURE", "1e-3")
    store = str(tmp_path / "family.jsonl")
    fam = pallas_family("probe", [8, 16], backend="interpret")
    c1 = Campaign(store, Controller(reps=2))
    for region in fam:
        c1.characterize(region, ["fp"])
    assert c1.stats.measured > 0

    fam2 = pallas_family("probe", [8, 16], backend="interpret")
    c2 = Campaign(store, Controller(reps=2))
    reps = {r.name: c2.characterize(r, ["fp"]) for r in fam2}
    assert c2.stats.measured == 0              # whole family replayed
    assert set(reps) == {"pallas_probe_s8", "pallas_probe_s16"}


def test_pallas_rt_callable_is_memoized_on_controller():
    """The controller's _rt_cache must hand the sensitivity probe and the
    sweep the SAME Pallas executable (one compile, not two)."""
    region, traces = _counting_region("probe", n_steps=8)
    ctl = Controller(reps=2, verify_payload=False)
    fn = ctl._rt_fn(region, "fp")
    assert fn is ctl._rt_fn(region, "fp")
    fn(jnp.int32(2), *region.args_for_rt("fp"))
    assert traces["n"] == 1


def _counting_family(kernel, sizes, **kw):
    traces = {"n": 0}
    fam = pallas_family(
        kernel, sizes, backend="interpret",
        trace_hook=lambda: traces.__setitem__("n", traces["n"] + 1), **kw)
    return fam, traces


def test_same_shaped_family_traces_once_per_mode():
    """The fig7 plane's q values have one shape: across both members'
    sweeps and payload checks each mode traces exactly once."""
    fam, traces = _counting_family("spmxv", [256], qs=[0.0, 1.0])
    ctl = Controller(reps=2, stop_ratio=100.0)
    for region in fam:
        for mode in KERNEL_MODES["spmxv"]:
            res = ctl.run_mode(region, mode, ks=(0, 2, 4))
            assert res.injection.ok()
            assert region.payload_check(mode, CHECK_K_MAX + 4).ok()
    assert traces["n"] == len(KERNEL_MODES["spmxv"])


@pytest.mark.parametrize("other", [{"n": 512}, {"n": 256, "br": 64}],
                         ids=["rows", "block"])
def test_another_shape_or_block_traces_its_own(other):
    """A member of another size (jax adds the shapes to the key), or of
    another block (a static value of the callable), traces its own."""
    traces = {"n": 0}

    def hook():
        traces["n"] += 1

    base = pallas_region("spmxv", backend="interpret", n=256,
                         trace_hook=hook)
    twin = pallas_region("spmxv", backend="interpret", n=256, q=1.0,
                         trace_hook=hook)
    odd = pallas_region("spmxv", backend="interpret", trace_hook=hook,
                        **other)
    for region, want in ((base, 1), (twin, 1), (odd, 2)):
        assert region.payload_check("fp", 4).ok()
        assert traces["n"] == want
    assert twin.build_rt("fp") is base.build_rt("fp")


def _float64_spmv(vals, cols, x):
    v, c, xx = (np.asarray(t, np.float64) for t in (vals, cols, x))
    return (v * xx[c.astype(np.int64)]).sum(axis=1)


@pytest.mark.parametrize("mode", sorted(KERNEL_MODES["spmxv"]))
def test_shared_runtime_k_callable_closes_over_no_buffer(mode):
    """After q=0 has built the shared callable, q=1 runs it on its own
    buffers: its payload check passes, and its main output is its own
    float64 product, not q=0's."""
    q0, q1 = pallas_family("spmxv", [256], qs=[0.0, 1.0],
                           backend="interpret")
    assert q0.payload_check(mode, 4).ok()
    assert q1.build_rt(mode) is q0.build_rt(mode)
    assert q1.payload_check(mode, 4).ok()

    outs = {}
    for q, region in ((0, q0), (1, q1)):
        args = region.args_for_rt(mode)
        y = np.asarray(region.build_rt(mode)(jnp.int32(4), *args)[0],
                       np.float64).reshape(-1)
        outs[q] = (y, _float64_spmv(*args))
    y1, ref1 = outs[1]
    y0, ref0 = outs[0]
    scale = np.max(np.abs(ref1))
    assert np.max(np.abs(y1 - ref1)) / scale < region_mod.REF_TOL["spmxv"]
    assert np.max(np.abs(y1 - y0)) / scale > 1e-2
    assert np.max(np.abs(y0 - ref0)) / np.max(np.abs(ref0)) \
        < region_mod.REF_TOL["spmxv"]


def test_runtime_k_resolution_reports_reuse(monkeypatch):
    """Each region's runtime-k resolution opens the regions layer's span
    with ``reused`` false where it built the shared callable, true where it
    found it; the static block values and the mode are part of the key."""
    opened = []

    @contextlib.contextmanager
    def stub(name, **meta):
        opened.append((name, meta))
        yield

    monkeypatch.setattr(region_mod, "span", stub)

    def hook():
        pass

    fam = pallas_family("spmxv", [256], qs=[0.0, 1.0], backend="interpret",
                        trace_hook=hook)
    for region in fam:
        for mode in KERNEL_MODES["spmxv"]:
            region.build_rt(mode)
            region.build_rt(mode)        # once per (region, mode)
    resolved = [(m["region"], m["kernel"], m["mode"], m["reused"])
                for name, m in opened
                if name == "campaign.region" and "mode" in m]
    q0, q1 = (r.name for r in fam)
    assert resolved == [(q0, "spmxv", "fp", False),
                        (q0, "spmxv", "vmem", False),
                        (q1, "spmxv", "fp", True),
                        (q1, "spmxv", "vmem", True)]
