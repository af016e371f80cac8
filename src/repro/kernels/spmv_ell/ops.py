"""jit'd public wrapper with backend dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpreter
from repro.kernels.spmv_ell.kernel import spmv_ell_pallas, spmv_ell_pallas_rt
from repro.kernels.spmv_ell.ref import spmv_ell_ref


@partial(jax.jit, static_argnames=("br", "mode", "k_noise", "backend"))
def spmv_ell(vals, cols, x, *, br: int = 128, mode: str = "none",
             k_noise: int = 0, backend: str = "pallas"):
    """ELL SPMV. Returns (y (R,), nacc (8,128))."""
    if backend == "ref":
        return spmv_ell_ref(vals, cols, x), jnp.zeros((8, 128), jnp.float32)
    return spmv_ell_pallas(vals, cols, x, br=br, mode=mode, k_noise=k_noise,
                           interpret=use_interpreter(backend))


@partial(jax.jit, static_argnames=("br", "mode", "backend"))
def spmv_ell_rt(k, vals, cols, x, *, br: int = 128, mode: str = "fp",
                backend: str = "pallas"):
    """Runtime-k ELL SPMV: ``k`` is a traced int32 operand (compile-once
    sweeps), pattern-identical to ``spmv_ell(..., k_noise=k)`` for
    k ≤ noise_slots.K_MAX."""
    return spmv_ell_pallas_rt(k, vals, cols, x, br=br, mode=mode,
                              interpret=use_interpreter(backend))