"""The paper's tool pointed at this framework's own training step: inject
noise into a (reduced) gemma train step, measure absorption, verify the
payload survived XLA, classify the bottleneck — then show the analytic
prediction for the same architecture at full scale on the TPU v5e target.

    PYTHONPATH=src python examples/probe_train_step.py
"""
import jax

from repro.configs import TPU_V5E, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import (Controller, StepTerms, classify, predict_absorption,
                        step_region)
from repro.core.noise import NoiseScale, make_modes
from repro.models.model import build

cfg = get_smoke_config("gemma_2b")
api = build(cfg)
params = api.init(jax.random.PRNGKey(0))
batch = api.dummy_batch(ShapeConfig("probe", "train", 128, 4))

modes = make_modes(NoiseScale(mxu_dim=64, hbm_mib=16, chase_len=1 << 18))

print("== measured (host backend, reduced config) ==")
names = ("fp_add32", "mxu_fma128", "vmem_ld", "hbm_stream")
region = step_region("gemma_2b_train", lambda p, b: api.loss(p, b)[0],
                     (params, batch), {name: modes[name] for name in names})
ctl = Controller(reps=3)
absorptions = {}
for name in names:
    res = ctl.run_mode(region, name)
    absorptions[name] = res.fit.k1
    print(f"  {name:12s} Abs^raw={res.fit.k1:7.1f}  "
          f"payload={res.injection.payload}/{res.injection.expected} "
          f"overhead={res.injection.overhead_fraction:.0%}")
print(" ", classify(absorptions))

print("\n== analytic (full gemma-2b train_4k on 256x TPU v5e) ==")
print("   terms from the dry-run artifact (run repro.launch.dryrun first for")
print("   live numbers; using representative values here):")
terms = StepTerms(compute=1.5e-3, memory=18e-3, ici=1.7e-3)
pred = {}
for name in ("fp_add32", "mxu_fma128", "vmem_ld", "hbm_stream"):
    fit = predict_absorption(terms, modes[name], TPU_V5E)
    pred[name] = fit.k1
    tag = "unbounded" if fit.k1 >= (1 << 20) else f"{fit.k1:9.0f}"
    print(f"  {name:12s} Abs^raw={tag}")
print(" ", classify(pred, high=1000.0))
print("\nThe memory term dominates at full scale (XLA attention materializes")
print("score tensors) -> hbm_stream noise is not absorbed; that is the")
print("bottleneck the flash-attention path removes.")
