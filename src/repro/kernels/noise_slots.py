"""Kernel-level noise slots — instruction-granularity injection inside Pallas
kernels (the closest TPU analogue of the paper's inline-asm patterns).

Every noisy kernel in this package takes a trailing ``noise_ref`` input block
(128×128, disjoint from kernel semantics — the paper's R_n ∩ R_s = ∅) and a
dedicated ``nacc`` output block (8×128) that all grid steps revisit; the
accumulated noise value is the DCE-proof aux output AND a correctness oracle
(its exact value is predictable, so tests assert the payload executed).

Modes (DESIGN.md §2 table):
  fp    — k VPU vector adds on the accumulator              (fp_add64)
  mxu   — k small (8×128)·(128×128) MXU dots                (fp FMA throughput)
  vmem  — k re-reads of the kernel's own input block at
          rotating offsets (always VMEM-resident)           (l1_ld64)

HBM-level noise is injected at the graph level (core.noise) — inside a Pallas
kernel every ref the body touches is already VMEM-resident by construction,
so "memory noise" belongs to the pipeline/DMA layer, not the body.

Runtime-k protocol (compile-once sweeps)
----------------------------------------
``emit_noise`` bakes ``k`` into the trace as a static Python int — the
paper's cost model, one Mosaic compile per sweep point. ``emit_noise_rt`` is
its compile-once twin: ``k`` is a TRACED int32 scalar, delivered to the
kernel as a scalar-prefetch operand (``compat.prefetch_scalar_grid_spec``,
the SMEM scalar ref that is resident before the body runs), and the patterns
are emitted by a bounded ``lax.fori_loop``:

  * the trip count is ``clip(k, 0, K_MAX)`` — ``K_MAX`` caps the payload a
    single grid step can emit (the controller's widest sweep tops out at
    k=320, comfortably inside the bound) so the accumulator oracle stays
    exact and a corrupt/hostile k cannot run the kernel away;
  * pattern j of the runtime path computes EXACTLY the arithmetic of pattern
    j of the static path (same addends, same offsets, same order), so for
    any k ≤ K_MAX the two paths are bitwise identical — asserted per kernel
    and mode in tests/test_kernels.py;
  * payload verification needs no static trace here: the compiled runtime-k
    HLO holds ONE pattern in a loop body, so surviving ops cannot be counted
    on it, but the exact ``nacc`` oracle can be checked on its output — one
    executable per (kernel, mode) sweep, payload check included;
  * a region that cannot thread a traced k — e.g. a hand-rolled
    ``pallas_call`` without the scalar-prefetch operand, or a k that changes
    buffer SHAPES rather than a loop trip count — has no ``build_rt``, and
    the Controller refuses to measure it: every sweep takes k at run time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NOISE_SHAPE = (8, 128)          # one VREG row group
NOISE_REF_SHAPE = (128, 128)    # MXU-aligned noise operand

MODES = ("none", "fp", "mxu", "vmem")

# Upper bound on the runtime noise quantity a single grid step may emit.
# ``emit_noise_rt`` clips its traced k to [0, K_MAX]; every controller sweep
# schedule stays below it (max scheduled k: 320).
K_MAX = 512


def noise_in_spec(grid_ndim: int) -> pl.BlockSpec:
    """The (128,128) noise operand: same block for every grid step.

    The star-args index map also absorbs the trailing scalar-prefetch ref
    on the runtime-k path, so one spec serves both.
    """
    return pl.BlockSpec(NOISE_REF_SHAPE, lambda *ids: (0, 0))


def noise_out_spec(grid_ndim: int) -> pl.BlockSpec:
    """The (8,128) noise accumulator: all grid steps revisit block (0,0)."""
    return pl.BlockSpec(NOISE_SHAPE, lambda *ids: (0, 0))


def noise_out_shape(dtype=jnp.float32):
    import jax

    return jax.ShapeDtypeStruct(NOISE_SHAPE, dtype)


def init_noise(nacc_ref, is_first):
    @pl.when(is_first)
    def _():
        nacc_ref[...] = jnp.zeros_like(nacc_ref)


def k_operand(k) -> jax.Array:
    """Shape the (possibly traced) noise quantity into the (1,) int32 array
    the scalar-prefetch slot expects."""
    return jnp.reshape(jnp.asarray(k, jnp.int32), (1,))


def _fp_c(noise_ref, src_ref):
    """The (8,128) addend of one fp pattern.

    With a dedicated noise operand: its first row group. Without one
    (``noise_ref=None`` — e.g. spmv_ell), the addend is derived from a
    RUNTIME block of the kernel's own input: a compile-time-constant addend
    would let the compiler strength-reduce the k-iteration add chain to one
    ``nacc += k*c`` (killing the payload the sweep is supposed to measure),
    while a data-dependent addend keeps every add live AND keeps the exact
    ``nacc`` oracle (tests derive the same value from the host copy).

    ``REPRO_NOISE_SABOTAGE=const`` deliberately reintroduces that bug — a
    compile-time-constant addend — so the static audit's fail-fast path
    (``repro.analysis``, the CI audit-smoke job) can be exercised against a
    payload XLA really does fold away. Never set it in a measuring run.
    """
    if os.environ.get("REPRO_NOISE_SABOTAGE") == "const":
        return jnp.full(NOISE_SHAPE, 1.0, jnp.float32)
    if noise_ref is not None:
        return noise_ref[0:8, :]
    if src_ref is None:
        raise ValueError("fp noise needs a noise operand or a src_ref to "
                         "derive its addend from")
    col = src_ref[0:8, 0:1].astype(jnp.float32)
    return jnp.broadcast_to(col, NOISE_SHAPE)


def _vmem_width(src) -> int:
    """vmem patterns read ``(8, w)`` blocks: full 128 lanes when the source
    block is wide enough, its own width otherwise (e.g. narrow ELL blocks)."""
    return min(src.shape[1], NOISE_SHAPE[1])


def emit_noise(mode: str, k: int, nacc_ref, noise_ref, src_ref=None,
               step=0) -> None:
    """Emit ``k`` patterns of ``mode`` into the kernel body (k static).

    ``step``: a traced or static per-grid-step index used to rotate vmem
    offsets (defeats CSE the same way the paper rotates registers).
    """
    if mode == "none" or k == 0:
        return
    if mode == "fp":
        c = _fp_c(noise_ref, src_ref)
        for _ in range(k):
            nacc_ref[...] += c
    elif mode == "mxu":
        a = noise_ref[0:8, :]
        b = noise_ref[...]
        for _ in range(k):
            nacc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32
                                     ).astype(nacc_ref.dtype)
    elif mode == "vmem":
        src = src_ref if src_ref is not None else noise_ref
        rows = src.shape[0]
        w = _vmem_width(src)
        for j in range(k):
            off = (step * 7 + j * 13) % max(rows - 8, 1)
            blk = src[pl.ds(off, 8), 0:w]
            nacc_ref[0:8, 0:w] += blk.astype(nacc_ref.dtype)
    else:
        raise ValueError(f"unknown kernel noise mode {mode!r}; one of {MODES}")


def emit_noise_rt(mode: str, k, nacc_ref, noise_ref, src_ref=None,
                  step=0, k_max: int = K_MAX) -> None:
    """``emit_noise`` with ``k`` a TRACED int32 scalar (runtime-k protocol).

    Patterns come out of a bounded ``lax.fori_loop`` whose trip count is
    ``clip(k, 0, k_max)``; iteration j performs exactly the arithmetic of
    static pattern j (same addends/offsets, same order), so the two paths
    are bitwise identical for any k ≤ ``k_max``. One compiled executable
    serves the whole k-sweep.
    """
    if mode == "none":
        return
    kk = jnp.clip(jnp.asarray(k, jnp.int32), 0, k_max)
    if mode == "fp":
        c = _fp_c(noise_ref, src_ref)
        nacc_ref[...] = jax.lax.fori_loop(
            0, kk, lambda j, acc: acc + c, nacc_ref[...])
    elif mode == "mxu":
        a = noise_ref[0:8, :]
        b = noise_ref[...]

        def one(j, acc):
            return acc + jnp.dot(a, b, preferred_element_type=jnp.float32
                                 ).astype(acc.dtype)

        nacc_ref[...] = jax.lax.fori_loop(0, kk, one, nacc_ref[...])
    elif mode == "vmem":
        src = src_ref if src_ref is not None else noise_ref
        rows = src.shape[0]
        w = _vmem_width(src)

        def one(j, acc):
            off = (step * 7 + j * 13) % max(rows - 8, 1)
            blk = src[pl.ds(off, 8), 0:w].astype(acc.dtype)
            if w < NOISE_SHAPE[1]:
                # zero-pad to full lanes instead of acc.at[:, :w].add —
                # the scatter that .at lowers to captures a rank-1 index
                # constant, which pallas_call rejects; lanes >= w only ever
                # hold +0.0, so the pad-add is bitwise-identical
                blk = jnp.pad(blk, ((0, 0), (0, NOISE_SHAPE[1] - w)))
            return acc + blk

        nacc_ref[...] = jax.lax.fori_loop(0, kk, one, nacc_ref[...])
    else:
        raise ValueError(f"unknown kernel noise mode {mode!r}; one of {MODES}")


def expected_fp_noise(noise: jnp.ndarray, k: int, n_steps: int) -> jnp.ndarray:
    """Oracle for mode='fp': nacc = k * n_steps * noise[0:8, :]."""
    return k * n_steps * noise[0:8, :].astype(jnp.float32)
