"""jit'd public wrapper with backend dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpreter
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  flash_attention_pallas_rt)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.noisy_matmul.ops import default_noise_operand


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "mode",
                                   "k_noise", "backend"))
def flash_attention(q, k, v, noise=None, *, causal: bool = True,
                    window: int = 0, bq: int = 128, bk: int = 128,
                    mode: str = "none", k_noise: int = 0,
                    backend: str = "pallas"):
    """Blocked attention. Returns (out, nacc)."""
    if noise is None:
        noise = default_noise_operand(jnp.float32)
    if backend == "ref":
        return (attention_ref(q, k, v, causal=causal, window=window),
                jnp.zeros((8, 128), jnp.float32))
    return flash_attention_pallas(q, k, v, noise, causal=causal,
                                  window=window, bq=bq, bk=bk, mode=mode,
                                  k_noise=k_noise,
                                  interpret=use_interpreter(backend))


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "mode",
                                   "backend"))
def flash_attention_rt(kq, q, k, v, noise=None, *, causal: bool = True,
                       window: int = 0, bq: int = 128, bk: int = 128,
                       mode: str = "fp", backend: str = "pallas"):
    """Runtime-k blocked attention: ``kq`` is a traced int32 noise quantity
    (compile-once sweeps), pattern-identical to
    ``flash_attention(..., k_noise=kq)`` for kq ≤ noise_slots.K_MAX."""
    if noise is None:
        noise = default_noise_operand(jnp.float32)
    return flash_attention_pallas_rt(kq, q, k, v, noise, causal=causal,
                                     window=window, bq=bq, bk=bk, mode=mode,
                                     interpret=use_interpreter(backend))
