"""jit'd public wrapper with backend dispatch."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.backend import use_interpreter
from repro.kernels.noise_probes.kernel import probe_pallas, probe_pallas_rt
from repro.kernels.noise_probes.ref import probe_ref
from repro.kernels.noisy_matmul.ops import default_noise_operand


@partial(jax.jit, static_argnames=("mode", "k_noise", "n_steps", "backend"))
def run_probe(noise=None, *, mode: str = "fp", k_noise: int = 1,
              n_steps: int = 128, backend: str = "pallas"):
    if noise is None:
        noise = default_noise_operand()
    if backend == "ref":
        return probe_ref(noise, mode=mode, k_noise=k_noise, n_steps=n_steps)
    return probe_pallas(noise, mode=mode, k_noise=k_noise, n_steps=n_steps,
                        interpret=use_interpreter(backend))


@partial(jax.jit, static_argnames=("mode", "n_steps", "backend"))
def run_probe_rt(k, noise=None, *, mode: str = "fp", n_steps: int = 128,
                 backend: str = "pallas"):
    """Runtime-k calibration probe: ``k`` is a traced int32 operand, so the
    per-pattern-cost sweep reuses one executable per mode."""
    if noise is None:
        noise = default_noise_operand()
    return probe_pallas_rt(k, noise, mode=mode, n_steps=n_steps,
                           interpret=use_interpreter(backend))
