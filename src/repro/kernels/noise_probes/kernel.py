"""Pure-noise calibration kernels.

On real TPU hardware, timing ``run_probe(mode, k, n_steps)`` against k gives
the per-pattern cost δ of each noise mode — the constant the analytic
saturation model needs (core.analytic.pattern_deltas provides spec-sheet
values; this kernel measures them). On CPU the kernel validates in interpret
mode: the accumulated value is exactly predictable, proving each pattern
executed exactly once (payload check at the arithmetic level).

``probe_pallas_rt`` is the compile-once twin: the noise quantity is a
scalar-prefetch int32 operand (runtime-k protocol, see noise_slots) — the
calibration sweep over k reuses ONE executable per mode.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels import noise_slots as ns


def _probe_kernel(noise_ref, nacc_ref, *, mode: str, k_noise: int):
    i = pl.program_id(0)
    ns.init_noise(nacc_ref, i == 0)
    ns.emit_noise(mode, k_noise, nacc_ref, noise_ref, src_ref=noise_ref,
                  step=i)


def _probe_kernel_rt(k_ref, noise_ref, nacc_ref, *, mode: str):
    i = pl.program_id(0)
    ns.init_noise(nacc_ref, i == 0)
    ns.emit_noise_rt(mode, k_ref[0], nacc_ref, noise_ref, src_ref=noise_ref,
                     step=i)


def probe_pallas(noise, *, mode: str, k_noise: int, n_steps: int,
                 interpret: bool = False):
    kernel = functools.partial(_probe_kernel, mode=mode, k_noise=k_noise)
    return pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[ns.noise_in_spec(1)],
        out_specs=ns.noise_out_spec(1),
        out_shape=ns.noise_out_shape(),
        interpret=interpret,
        name="noise_probe",
    )(noise)


def probe_pallas_rt(k, noise, *, mode: str, n_steps: int,
                    interpret: bool = False):
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(n_steps,),
        in_specs=[ns.noise_in_spec(1)],
        out_specs=ns.noise_out_spec(1),
    )
    return pl.pallas_call(
        functools.partial(_probe_kernel_rt, mode=mode),
        grid_spec=grid_spec,
        out_shape=ns.noise_out_shape(),
        interpret=interpret,
        name="noise_probe_rt",
    )(ns.k_operand(k), noise)
