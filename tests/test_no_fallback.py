"""No path hides the device or a failed check: a chip backend without a chip
raises, a payload check that raises or fails fails its pair, ICI noise
without a mesh axis raises, local fleet shards stay in the process that
holds the accelerator, and the compile cache sits at one fixed place."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Campaign, Controller, RegionTarget
from repro.core.controller import PayloadError
from repro.core.noise import NoiseScale, make_modes
from repro.core.payload import InjectionReport, analyze_injection
from repro.kernels.backend import NoChipError
from repro.kernels.noisy_matmul.ops import noisy_matmul
from repro.kernels.region import (CHECK_K_MAX, REF_TOL, oracle_rtol,
                                  pallas_family, pallas_region)


# ---------------------------------------------------------------- backends

@pytest.mark.parametrize("backend", [None, "pallas"], ids=["default",
                                                              "pallas"])
def test_chip_backend_without_a_chip_raises(backend):
    assert jax.default_backend() != "tpu"
    kw = {} if backend is None else {"backend": backend}
    with pytest.raises(NoChipError, match="finds none"):
        pallas_region("matmul", n=128, **kw)
    with pytest.raises(NoChipError):
        pallas_family("probe", [8], **kw)
    a = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(NoChipError):
        noisy_matmul(a, a, bm=128, bn=128, bk=128, **kw)


@pytest.mark.parametrize("backend", ["cuda", "auto"])
def test_unknown_backend_is_refused(backend):
    with pytest.raises(ValueError, match="unknown pallas backend"):
        pallas_region("probe", backend=backend, n_steps=8)


def test_plan_refuses_an_unknown_backend(tmp_path):
    from repro.fleet.plan import PlanError, SweepPlan, TargetSpec

    with pytest.raises(PlanError, match="backend 'auto' unknown"):
        SweepPlan(name="p", store=str(tmp_path / "s.jsonl"),
                  targets=[TargetSpec("pallas", ("fp",),
                                      {"kernel": "probe", "sizes": [8]})],
                  backend="auto").validate()


# ---------------------------------------------------------- payload checks

def _region(payload_check):
    def build(mode, k):
        return jax.jit(lambda x: x * 2.0 + k)

    x = jnp.ones((8, 128), jnp.float32)
    return RegionTarget(name="r", build=build, args_for=lambda m, k: (x,),
                        body_size=1, payload_check=payload_check,
                        build_rt=lambda m: jax.jit(lambda k, x: x * 2.0 + k),
                        args_for_rt=lambda m: (x,))


def _report(payload, expected=4, **kw):
    return InjectionReport(mode="fp", target="compute", expected=expected,
                           payload=payload, overhead=0,
                           payload_dynamic=payload, body_ops=1, **kw)


def test_raising_payload_check_fails_the_pair(tmp_path):
    def boom(mode, k):
        raise RuntimeError("Mosaic refused the kernel")

    region = _region(boom)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        Controller(reps=1).run_mode(region, "fp", ks=(0, 2, 4))
    camp = Campaign(str(tmp_path / "s.jsonl"), Controller(reps=1))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        camp.sweep_mode(region, "fp")
    assert not camp.store.is_done("r", "fp")     # no done record: resumable
    camp.store.close()


@pytest.mark.parametrize("report", [
    _report(0),                                    # the noise did not run
    _report(4, ref_err=0.5, ref_tol=1e-2),         # the kernel is wrong
], ids=["payload-dead", "output-off-reference"])
def test_failing_payload_report_fails_the_pair(report):
    region = _region(lambda mode, k: report)
    with pytest.raises(PayloadError, match="payload check failed"):
        Controller(reps=1).run_mode(region, "fp", ks=(0, 2, 4))


def test_passing_payload_report_is_returned():
    region = _region(lambda mode, k: _report(4, ref_err=1e-6, ref_tol=1e-2))
    res = Controller(reps=1).run_mode(region, "fp", ks=(0, 2, 4))
    assert res.injection.ok() and res.injection.ref_err == 1e-6


def test_pallas_payload_check_holds_main_output_to_reference():
    region = pallas_region("spmxv", backend="interpret", n=256)
    rep = region.payload_check("fp", 40)
    assert rep.expected == CHECK_K_MAX              # capped pattern count
    assert rep.ref_tol is not None and rep.ref_err < rep.ref_tol
    assert rep.ok()


def test_pallas_payload_check_also_runs_the_swept_runtime_k_build():
    traces = {"n": 0}
    region = pallas_region(
        "matmul", backend="interpret", n=128,
        trace_hook=lambda: traces.__setitem__("n", traces["n"] + 1))
    assert region.payload_check("fp", 40).ok()
    assert traces["n"] == 1             # runtime-k counts and sweeps alike
    region.build_rt("fp")(jnp.int32(3), *region.args_for_rt("fp"))
    assert traces["n"] == 1             # the sweep's own executable


@pytest.mark.parametrize("kernel", ["matmul", "attention"])
def test_ref_tol_separates_f32_products_from_one_bf16_pass(kernel):
    """The MXU kernels ask for float32 products; one bf16 pass of the same
    operands (Mosaic's default precision) must fail ``REF_TOL``."""
    from repro.kernels.flash_attention.ref import attention_ref

    sizes = {"matmul": {"n": 256},
             "attention": {"seq": 128, "heads": 2, "head_dim": 64}}[kernel]
    region = pallas_region(kernel, backend="interpret", **sizes)
    rep = region.payload_check("fp", 4)
    assert rep.ref_err < REF_TOL[kernel]

    args = [jnp.asarray(a) for a in region.args_for("fp", 0)[:-1]]
    one_pass = [a.astype(jnp.bfloat16).astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        fn = jnp.dot if kernel == "matmul" else attention_ref
        ref, ctl = (np.asarray(fn(*xs), np.float64) for xs in (args,
                                                                one_pass))
    err = np.max(np.abs(ctl - ref)) / np.max(np.abs(ref))
    assert err > REF_TOL[kernel]


def test_oracle_tolerance_grows_with_adds_but_catches_a_missing_pattern():
    assert oracle_rtol(100) == 1e-4
    # the chip-size matmul: 32768 grid steps at CHECK_K_MAX patterns
    n_adds = CHECK_K_MAX * 32768
    assert oracle_rtol(n_adds) < 1.0 / CHECK_K_MAX


# --------------------------------------------------------------- ICI noise

@pytest.mark.parametrize("mode", ["ici_allreduce", "ici_allgather",
                                  "ici_a2a"])
@pytest.mark.parametrize("runtime_k", [False, True], ids=["static", "rt"])
def test_ici_mode_without_a_mesh_axis_raises(mode, runtime_k):
    m = make_modes(NoiseScale(ici_kib=4))[mode]
    state = m.make_state(jax.random.PRNGKey(0))
    apply = m.apply_rt if runtime_k else m.apply
    with pytest.raises(ValueError, match="needs a mesh with that axis"):
        apply(state, 2)


_COMBINED_HLO = """\
HloModule m, entry_computation_layout={()->f32[4]}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="noise_pattern/psum"}
  %b = f32[] parameter(1), metadata={op_name="noise_pattern/psum"}
  ROOT %add.0 = f32[] add(%a, %b), metadata={op_name="noise_pattern/add"}
}

ENTRY %main (p: f32[4], q: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %q = f32[4]{0} parameter(1)
  %all-reduce.1 = (f32[4]{0}, f32[4]{0}) all-reduce(%p, %q), to_apply=%sum, metadata={op_name="jit(f)/dot_general"}
  %gte.0 = f32[4]{0} get-tuple-element(%all-reduce.1), index=0, metadata={op_name="jit(f)/dot_general"}
  %gte.1 = f32[4]{0} get-tuple-element(%all-reduce.1), index=1, metadata={op_name="jit(f)/noise_pattern/psum"}
  %psum.2 = f32[4]{0} all-reduce(%gte.1), to_apply=%sum, metadata={op_name="jit(f)/noise_pattern/psum"}
  ROOT %mul.3 = f32[4]{0} multiply(%psum.2, %gte.0), metadata={op_name="jit(f)/noise_pattern/mul"}
}
"""


def test_payload_counts_a_noise_collective_combined_with_the_steps():
    """XLA's combiner may fold the first noise all-reduce into the step's
    own (one tuple op tagged as the step's); its noise-tagged element read
    still names it. The reduction body is the collective's, not overhead."""
    rep = analyze_injection(_COMBINED_HLO, mode="ici_allreduce",
                            target="ici", expected=2)
    assert (rep.payload, rep.overhead) == (2, 1)
    assert rep.ok()


_ASYNC_HLO = """\
HloModule m, entry_computation_layout={()->f32[16]}

%start (p0: f32[4]) -> (f32[4], f32[16]) {
  %p0 = f32[4]{0} parameter(0)
  %all-gather.1 = f32[16]{0} all-gather(%p0), channel_id=1, dimensions={0}, metadata={op_name="jit(f)/noise_pattern/all_gather"}
  ROOT %custom-call.1 = (f32[4]{0}, f32[16]{0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%overlapped (p1: f32[4]) -> f32[16] {
  %p1 = f32[4]{0} parameter(0)
  ROOT %all-gather.2 = f32[16]{0} all-gather(%p1), channel_id=1, dimensions={0}, metadata={op_name="jit(f)/noise_pattern/all_gather"}
}

%done (p2: f32[4], p3: f32[16]) -> f32[16] {
  %p2 = f32[4]{0} parameter(0)
  %p3 = f32[16]{0} parameter(1)
  %all-gather.3 = f32[16]{0} all-gather(%p2), channel_id=1, dimensions={0}, metadata={op_name="jit(f)/noise_pattern/all_gather"}
  ROOT %custom-call.3 = f32[16]{0} custom-call(%p2, %p3, %all-gather.3), custom_call_target="AsyncCollectiveDone"
}

ENTRY %main (p: f32[4]) -> f32[16] {
  %p = f32[4]{0} parameter(0)
  %s = (f32[4]{0}, f32[16]{0}) fusion(%p), kind=kCustom, calls=%start
  %o = f32[16]{0} fusion(%p), kind=kCustom, calls=%overlapped
  %d = f32[16]{0} fusion(%p, %o), kind=kCustom, calls=%done
  %all-gather.4 = f32[16]{0} all-gather(%p), channel_id=1, dimensions={0}, metadata={op_name="jit(f)/noise_pattern/all_gather"}
  ROOT %add.5 = f32[16]{0} add(%d, %all-gather.4), metadata={op_name="jit(f)/add"}
}
"""


def test_payload_counts_an_async_collective_once():
    """The TPU compiler clones an async collective into its start and done
    fusions; only the overlapped one is the collective."""
    rep = analyze_injection(_ASYNC_HLO, mode="ici_allgather", target="ici",
                            expected=2)
    assert (rep.payload, rep.overhead) == (2, 0)
    assert rep.ok()
    assert not analyze_injection(_ASYNC_HLO, mode="ici_allgather",
                                 target="ici", expected=1).ok()


@pytest.mark.parametrize("payload,overhead,target,ok", [
    (4, 0, "compute", True),
    (8, 0, "vmem", True),          # a VMEM pattern loads and adds
    (4, 5, "compute", False),      # the noise came with more overhead
    (4, 4, "ici", True),           # one local op per collective
    (5, 4, "ici", False),          # more collectives than patterns
], ids=["exact", "two-ops-per-pattern", "overhead", "ici-local-op",
        "ici-extra"])
def test_payload_report_gates(payload, overhead, target, ok):
    rep = InjectionReport(mode="m", target=target, expected=4,
                          payload=payload, overhead=overhead,
                          payload_dynamic=payload, body_ops=1)
    assert rep.ok() is ok


# ----------------------------------------------------------- fleet launch

def test_local_shards_run_in_the_process_holding_the_accelerator(
        tmp_path, monkeypatch):
    from repro.fleet import launchers
    from repro.fleet.plan import SweepPlan, TargetSpec

    plan = SweepPlan(name="p", store=str(tmp_path / "s.jsonl"),
                     targets=[TargetSpec("pallas", ("fp",),
                                         {"kernel": "probe", "sizes": [8]})],
                     shards=2, backend="interpret")
    ran = []
    monkeypatch.setattr(launchers, "holds_accelerator", lambda: True)
    monkeypatch.setattr(launchers, "_run_worker_inline",
                        lambda path, p, i: ran.append(i) or 0)

    def no_spawn(*a, **k):
        raise AssertionError("a child process would compete for the chip")

    monkeypatch.setattr(launchers.subprocess, "Popen", no_spawn)
    out = launchers.LocalLauncher().launch("plan.json", plan, [0, 1])
    assert ran == [0, 1] and {i: o.rc for i, o in out.items()} == {0: 0,
                                                                    1: 0}


def test_cpu_parent_does_not_hold_an_accelerator():
    from repro.fleet.launchers import holds_accelerator

    assert holds_accelerator() is False


# ------------------------------------------------------- compile cache

def test_compile_cache_honours_the_environment(monkeypatch):
    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # left alone


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    from repro import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.setup_compile_cache()
        second = compile_cache.setup_compile_cache()
        assert first == second == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == first
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------- calibration

def test_calibration_key_is_the_device_kind():
    from repro.core.calibration import hw_name

    assert hw_name() == jax.devices()[0].device_kind


# ------------------------------------------------------------------ SPMXV

def test_spmxv_gather_reads_x_of_another_length():
    from repro.kernels.spmv_ell.ops import spmv_ell
    from repro.kernels.spmv_ell.ref import spmv_ell_ref

    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.random((256, 16), np.float32))
    cols = jnp.asarray(rng.integers(0, 300, (256, 16), np.int32))
    x = jnp.asarray(rng.standard_normal(300).astype(np.float32))
    y, _ = spmv_ell(vals, cols, x, br=128, backend="interpret")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(spmv_ell_ref(vals, cols, x)),
                               rtol=1e-5, atol=1e-5)


def test_spmxv_refuses_x_past_its_vmem_budget():
    from repro.kernels.spmv_ell.kernel import X_VMEM_BYTES, spmv_ell_pallas

    n = X_VMEM_BYTES // 4 + 128
    vals = jax.ShapeDtypeStruct((256, 16), jnp.float32)
    cols = jax.ShapeDtypeStruct((256, 16), jnp.int32)
    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    with pytest.raises(ValueError, match="keeps x whole in VMEM"):
        jax.eval_shape(lambda v, c, xx: spmv_ell_pallas(v, c, xx,
                                                        interpret=True),
                       vals, cols, x)
