"""jit'd public wrapper: backend dispatch + noise plumbing."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpreter
from repro.kernels.noise_slots import NOISE_REF_SHAPE
from repro.kernels.noisy_matmul.kernel import matmul_pallas, matmul_pallas_rt
from repro.kernels.noisy_matmul.ref import matmul_ref


def default_noise_operand(dtype=jnp.float32):
    return (jnp.arange(NOISE_REF_SHAPE[0] * NOISE_REF_SHAPE[1], dtype=jnp.float32)
            .reshape(NOISE_REF_SHAPE) * 1e-6).astype(dtype)


@partial(jax.jit, static_argnames=("mode", "k_noise", "bm", "bn", "bk",
                                   "backend"))
def noisy_matmul(a, b, noise=None, *, mode: str = "none", k_noise: int = 0,
                 bm: int = 256, bn: int = 256, bk: int = 256,
                 backend: str = "pallas"):
    """Matmul with optional kernel-level noise.

    backend: "pallas" (the TPU; raises without one), "interpret"
    (the Pallas interpreter, any backend), "ref" (oracle).
    Returns (out, nacc); nacc is zeros for mode="none".
    """
    if noise is None:
        noise = default_noise_operand(a.dtype)
    if backend == "ref":
        return matmul_ref(a, b), jnp.zeros((8, 128), jnp.float32)
    return matmul_pallas(a, b, noise, mode=mode, k_noise=k_noise,
                         bm=bm, bn=bn, bk=bk,
                         interpret=use_interpreter(backend))


@partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk", "backend"))
def noisy_matmul_rt(k, a, b, noise=None, *, mode: str = "fp",
                    bm: int = 256, bn: int = 256, bk: int = 256,
                    backend: str = "pallas"):
    """Runtime-k matmul: ``k`` is a traced int32 operand (compile-once
    sweeps). Pattern-for-pattern identical to ``noisy_matmul(..., k_noise=k)``
    for k ≤ noise_slots.K_MAX."""
    if noise is None:
        noise = default_noise_operand(a.dtype)
    return matmul_pallas_rt(k, a, b, noise, mode=mode, bm=bm, bn=bn, bk=bk,
                            interpret=use_interpreter(backend))
