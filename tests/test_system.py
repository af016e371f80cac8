"""End-to-end system tests: one real dry-run cell compiled on the 512-device
production mesh (subprocess — device count is locked at first jax init), the
roofline record it produces, and the measured-probe pipeline on a reduced
model."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest


def test_dryrun_one_cell_single_pod(tmp_path):
    """gemma-2b decode_32k lowers + compiles on the 16x16 mesh and yields
    sane memory/roofline numbers."""
    code = (
        "from repro.launch.dryrun import run_cell\n"
        "rec = run_cell('gemma_2b', 'decode_32k', multi_pod=False,"
        f" out_dir={str(tmp_path)!r}, verbose=False)\n"
        "import json; print(json.dumps(rec['status']))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560,
                         env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.load(open(tmp_path / "16x16" / "gemma_2b_decode_32k.json"))
    assert rec["status"] == "ok", rec.get("error")
    r = rec["roofline"]
    assert r["flops_per_chip"] > 0
    assert r["hbm_bytes_per_chip"] > 0
    assert rec["memory"]["argument_size_in_bytes"] > 0
    # per-chip argument bytes must fit v5e HBM
    assert rec["memory"]["argument_size_in_bytes"] < 16 * 2**30


def test_probe_end_to_end_measured():
    """The paper's tool against a real (reduced) train step: absorption
    sweeps, payload verification, classification."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.core import Controller, step_region
    from repro.core.noise import NoiseScale, make_modes
    from repro.models.model import build

    cfg = get_smoke_config("gemma_2b")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = api.dummy_batch(ShapeConfig("p", "train", 64, 2))

    modes = make_modes(NoiseScale(mxu_dim=32, hbm_mib=4, chase_len=1 << 16))
    region = step_region("gemma_2b_train", lambda p, b: api.loss(p, b)[0],
                         (params, batch), {"fp_add32": modes["fp_add32"]})
    res = Controller(reps=2).run_mode(region, "fp_add32", ks=(0, 2, 4, 8))
    assert res.injection.payload >= 4
    assert res.fit.t0 > 0
    assert len(res.curve.ks) >= 3


@pytest.mark.parametrize("ks", [(0, 2, 6), (0, 8, 24)],
                         ids=["below-cap", "above-cap"])
def test_step_region_payload_check_uses_controller_rule(ks):
    """A step probed through the Controller takes the fleet's payload rule:
    the census runs at the last nonzero k swept, capped at CHECK_K_MAX, and
    the outputs stay bit-identical to k=0."""
    from repro.core import Controller, step_region
    from repro.core.noise import NoiseScale, make_modes
    from repro.kernels.region import CHECK_K_MAX

    modes = make_modes(NoiseScale(mxu_dim=32, hbm_mib=4, chase_len=1 << 16))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    region = step_region("tiny_step", lambda a: jnp.tanh(a @ a.T), (x,),
                         {"fp_add32": modes["fp_add32"]})
    res = Controller(reps=2, stop_ratio=100.0).run_mode(region, "fp_add32",
                                                         ks=ks)
    assert res.curve.ks == list(ks)
    want = min(ks[-1], CHECK_K_MAX)
    assert res.injection.expected == res.injection.payload == want
    assert res.injection.ref_err == 0.0 and res.injection.ok()


def test_analytic_probe_from_record(tmp_path):
    """launch.probe --analytic consumes a dry-run record, persists pred
    records to its campaign store, and replays them on a re-run (the
    --expect-no-measure contract)."""
    from repro.launch.probe import analytic_probe

    rec = {"status": "ok", "mesh": "16x16",
           "roofline": {"t_compute": 2e-3, "t_memory": 8e-3, "t_ici": 1e-3,
                        "dominant": "memory"}}
    d = tmp_path / "16x16"
    d.mkdir()
    with open(d / "gemma_2b_train_4k.json", "w") as f:
        json.dump(rec, f)
    store = str(tmp_path / "pred.jsonl")
    analytic_probe("gemma-2b", "train_4k", str(d),
                   ["fp_add32", "hbm_stream"], tol=0.05, store=store)
    # second run must be pure replay — expect_no_measure raises otherwise
    analytic_probe("gemma-2b", "train_4k", str(d),
                   ["fp_add32", "hbm_stream"], tol=0.05, store=store,
                   expect_no_measure=True)
    # a tol change invalidates the stored predictions
    with pytest.raises(SystemExit, match="expect-no-measure"):
        analytic_probe("gemma-2b", "train_4k", str(d),
                       ["fp_add32", "hbm_stream"], tol=0.02, store=store,
                       expect_no_measure=True)


def test_benchmark_analytic_suite():
    """The pure-analytic benchmarks run and reproduce the paper findings."""
    from benchmarks import table4_memsys

    out = table4_memsys.run(quick=True)
    assert out["hbm_collapse"] is True


def test_loop_noise_composition():
    """noisy_loop: generic injection site wraps an arbitrary body."""
    from repro.core import make_loop_modes, noisy_loop

    modes = make_loop_modes()

    def body(i, acc):
        return acc + 1.0

    out, aux = jax.jit(
        lambda a: noisy_loop(body, 16, a, modes["fp_add"], k=2))(
            jnp.zeros((), jnp.float32))
    assert float(out) == 16.0
    assert jnp.isfinite(aux)
