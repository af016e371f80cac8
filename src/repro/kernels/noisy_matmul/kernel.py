"""Tiled TPU matmul with instruction-level noise slots.

Grid (M/bm, N/bn, K/bk), K innermost; f32 accumulator in VMEM scratch; block
shapes are MXU-aligned (multiples of 128 on the contracting/lane dims). The
noise slot runs after the tile FMA so the Mosaic scheduler is free to overlap
it with the next DMA — exactly the slack the absorption metric measures.

Two entry points share one body: ``matmul_pallas`` bakes ``k_noise`` into the
trace (one executable per sweep point — the paper's cost model), while
``matmul_pallas_rt`` takes k as a scalar-prefetch int32 operand and emits
patterns through the bounded runtime-k loop (``noise_slots.emit_noise_rt``) —
one executable serves the whole sweep, bitwise identical per (mode, k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels import noise_slots as ns

# star-args tails absorb the scalar-prefetch ref on the runtime-k path, so
# the same index maps serve both pallas_call signatures
_A_SPEC = lambda bm, bk: pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k))
_B_SPEC = lambda bk, bn: pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j))
_O_SPEC = lambda bm, bn: pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j))


def _mm_body(a_ref, b_ref, noise_ref, o_ref, nacc_ref, acc_ref, emit):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ns.init_noise(nacc_ref, (i == 0) & (j == 0) & (kk == 0))

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    # noise slot: after the FMA, before the writeback
    emit(nacc_ref, noise_ref, a_ref, i * 131 + j * 17 + kk)

    @pl.when(kk == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel(a_ref, b_ref, noise_ref, o_ref, nacc_ref, acc_ref, *,
               mode: str, k_noise: int):
    _mm_body(a_ref, b_ref, noise_ref, o_ref, nacc_ref, acc_ref,
             lambda nacc, nz, src, step: ns.emit_noise(
                 mode, k_noise, nacc, nz, src_ref=src, step=step))


def _mm_kernel_rt(k_ref, a_ref, b_ref, noise_ref, o_ref, nacc_ref, acc_ref,
                  *, mode: str):
    _mm_body(a_ref, b_ref, noise_ref, o_ref, nacc_ref, acc_ref,
             lambda nacc, nz, src, step: ns.emit_noise_rt(
                 mode, k_ref[0], nacc, nz, src_ref=src, step=step))


def _mm_shapes(a, b, bm, bn, bk):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape,
                                                        (bm, bn, bk))
    return M, N, K, bm, bn, bk


def matmul_pallas(a: jax.Array, b: jax.Array, noise: jax.Array, *,
                  mode: str = "none", k_noise: int = 0,
                  bm: int = 256, bn: int = 256, bk: int = 256,
                  interpret: bool = False):
    """a (M,K) @ b (K,N) -> (out (M,N), nacc (8,128) f32). Static k."""
    M, N, K, bm, bn, bk = _mm_shapes(a, b, bm, bn, bk)
    grid = (M // bm, N // bn, K // bk)

    kernel = functools.partial(_mm_kernel, mode=mode, k_noise=k_noise)
    out, nacc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _A_SPEC(bm, bk),
            _B_SPEC(bk, bn),
            ns.noise_in_spec(3),
        ],
        out_specs=[
            _O_SPEC(bm, bn),
            ns.noise_out_spec(3),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), a.dtype),
            ns.noise_out_shape(),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="noisy_matmul",
    )(a, b, noise)
    return out, nacc


def matmul_pallas_rt(k, a: jax.Array, b: jax.Array, noise: jax.Array, *,
                     mode: str = "fp",
                     bm: int = 256, bn: int = 256, bk: int = 256,
                     interpret: bool = False):
    """Runtime-k twin of ``matmul_pallas``: ``k`` is a traced int32 delivered
    via scalar prefetch; one executable serves the whole k-sweep."""
    M, N, K, bm, bn, bk = _mm_shapes(a, b, bm, bn, bk)
    grid = (M // bm, N // bn, K // bk)

    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            _A_SPEC(bm, bk),
            _B_SPEC(bk, bn),
            ns.noise_in_spec(3),
        ],
        out_specs=[
            _O_SPEC(bm, bn),
            ns.noise_out_spec(3),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out, nacc = pl.pallas_call(
        functools.partial(_mm_kernel_rt, mode=mode),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, N), a.dtype),
            ns.noise_out_shape(),
        ],
        interpret=interpret,
        name="noisy_matmul_rt",
    )(ns.k_operand(k), a, b, noise)
    return out, nacc
