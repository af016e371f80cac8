"""ELL-format sparse matrix-vector product for TPU — the paper's SPMXV case
study kernel (§6), adapted from CSR to the TPU-friendly ELL layout.

CSR's per-row variable nnz serializes badly on a vector unit; ELL pads every
row to L nonzeros so the kernel is a (br, L) gather + multiply-reduce. The
irregular part — the x gather through ``cols`` — is exactly what the paper's
swap probability q randomizes.

The gather is a real data-dependent read of x, one element per nonzero:
``cols`` arrives in SMEM (a (br*L,) block per grid step), so every column
index is a scalar that addresses x directly. x lives in VMEM as lane-chunked
rows (N/128, 128); index c selects row ``c >> 7`` with a dynamic sublane load
and a lane rotate moves element ``c & 127`` to the lane the nonzero occupies
in the gathered (br, L) block. Mosaic has no general vector gather (only
within one 128-lane tile), so the scalar unit drives the addressing.

Blocks: vals (br, L) in VMEM; cols (br*L,) int32 in SMEM; x whole-array in
VMEM, unpipelined: N*4 bytes (1 MiB at N = 2^18), refused past
``X_VMEM_BYTES``. y is written lane-major as rows of an (nb, br) array, 8
grid steps per (8, br) output block, so every block obeys the (8, 128)
tiling rule. Mosaic compiles L ≤ 128; wider rows run in the interpreter
only.

Noise: this kernel has no dedicated noise operand — fp noise derives its
addend from a RUNTIME block of ``vals`` (first rows of the current block;
``noise_slots._fp_c``). A compile-time-constant addend would let the
compiler strength-reduce the k-iteration add chain to one ``nacc += k*c``,
silently deleting the payload the sweep measures; the data-dependent addend
keeps every add live and keeps the exact ``nacc`` oracle
(``ref.fp_noise_ell_ref``). vmem noise re-reads the vals block at rotating
offsets. ``spmv_ell_pallas_rt`` is the compile-once twin (runtime-k protocol,
see noise_slots).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels import noise_slots as ns

LANES = 128
# x stays whole in VMEM; past this it would crowd the 16 MiB scoped limit
X_VMEM_BYTES = 8 << 20
# gather steps per loop trip: scalar address math of one overlaps the
# vector load/rotate of the next
_UNROLL = 8


def _gather(cols_ref, x_ref, g_ref, L: int) -> None:
    """g[r, l] = x[cols[r, l]] for every row r of the block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def row(r, carry):
        for c0 in range(0, L, LANES):
            w = min(LANES, L - c0)

            def one(j, acc):
                c = cols_ref[r * L + c0 + j]
                xrow = x_ref[pl.ds(jax.lax.shift_right_logical(c, 7), 1), :]
                hit = pltpu.roll(xrow.astype(jnp.float32),
                                 jnp.bitwise_and(j - c, LANES - 1), 1)
                return jnp.where(lane == j, hit, acc)

            def unrolled(b, acc):     # Mosaic unrolls fully or not at all
                for j in range(_UNROLL):
                    acc = one(b * _UNROLL + j, acc)
                return acc

            acc = jax.lax.fori_loop(0, w // _UNROLL, unrolled,
                                    jnp.zeros((1, LANES), jnp.float32))
            for j in range(w - w % _UNROLL, w):
                acc = one(j, acc)
            g_ref[pl.ds(r, 1), c0:c0 + w] = acc[:, :w]
        return carry

    jax.lax.fori_loop(0, g_ref.shape[0], row, 0)


def _spmv_body(vals_ref, cols_ref, x_ref, y_ref, nacc_ref, g_ref, emit, *,
               out_rows: int):
    i = pl.program_id(0)
    ns.init_noise(nacc_ref, i == 0)

    _gather(cols_ref, x_ref, g_ref, vals_ref.shape[1])
    y = jnp.sum(vals_ref[...].astype(jnp.float32) * g_ref[...], axis=1)
    y_ref[pl.ds(i % out_rows, 1), :] = y.reshape(1, -1).astype(y_ref.dtype)

    # noise slot: both modes feed off the vals block (fp derives its addend
    # from it, vmem re-reads it) — R_n ∩ R_s = ∅ still holds: nacc is a
    # dedicated output, vals is only ever read.
    emit(nacc_ref, vals_ref, i)


def _spmv_kernel(vals_ref, cols_ref, x_ref, y_ref, nacc_ref, g_ref, *,
                 mode: str, k_noise: int, out_rows: int):
    _spmv_body(vals_ref, cols_ref, x_ref, y_ref, nacc_ref, g_ref,
               lambda nacc, vals, step: ns.emit_noise(
                   mode, k_noise, nacc, None, src_ref=vals, step=step),
               out_rows=out_rows)


def _spmv_kernel_rt(k_ref, vals_ref, cols_ref, x_ref, y_ref, nacc_ref, g_ref,
                    *, mode: str, out_rows: int):
    _spmv_body(vals_ref, cols_ref, x_ref, y_ref, nacc_ref, g_ref,
               lambda nacc, vals, step: ns.emit_noise_rt(
                   mode, k_ref[0], nacc, None, src_ref=vals, step=step),
               out_rows=out_rows)


def _spmv_setup(vals, cols, x, br):
    """Shapes, specs and operands shared by the static and runtime-k calls."""
    R, L = vals.shape
    br = min(br, R)
    assert R % br == 0, (R, br)
    assert br >= 8, (br, "noise patterns read 8-row groups of the block")
    nb = R // br
    N = x.shape[0]
    n_pad = -(-N // LANES) * LANES
    if n_pad * 4 > X_VMEM_BYTES:
        raise ValueError(f"x of {N} elements needs {n_pad * 4} bytes of VMEM; "
                         f"this kernel keeps x whole in VMEM and allows "
                         f"{X_VMEM_BYTES}")
    out_rows = 8 if nb % 8 == 0 else nb
    x2d = jnp.pad(x.astype(jnp.float32), (0, n_pad - N)).reshape(-1, LANES)
    in_specs = [
        pl.BlockSpec((br, L), lambda i, *_: (i, 0)),
        pl.BlockSpec((br * L,), lambda i, *_: (i,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
    ]
    out_specs = [
        pl.BlockSpec((out_rows, br), lambda i, *_: (i // out_rows, 0)),
        ns.noise_out_spec(1),
    ]
    out_shape = [jax.ShapeDtypeStruct((nb, br), x.dtype),
                 ns.noise_out_shape()]
    scratch = [pltpu.VMEM((br, L), jnp.float32)]
    operands = (vals, cols.reshape(-1).astype(jnp.int32), x2d)
    return R, nb, out_rows, in_specs, out_specs, out_shape, scratch, operands


def spmv_ell_pallas(vals, cols, x, *, br: int = 128, mode: str = "none",
                    k_noise: int = 0, interpret: bool = False):
    """vals,cols (R,L); x (N,) -> (y (R,), nacc). Static k."""
    (R, nb, out_rows, in_specs, out_specs, out_shape, scratch,
     operands) = _spmv_setup(vals, cols, x, br)
    y, nacc = pl.pallas_call(
        functools.partial(_spmv_kernel, mode=mode, k_noise=k_noise,
                          out_rows=out_rows),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="spmv_ell",
    )(*operands)
    return y.reshape(R), nacc


def spmv_ell_pallas_rt(k, vals, cols, x, *, br: int = 128, mode: str = "fp",
                       interpret: bool = False):
    """Runtime-k twin of ``spmv_ell_pallas``: one executable per mode serves
    the whole k-sweep (scalar-prefetch delivery)."""
    (R, nb, out_rows, in_specs, out_specs, out_shape, scratch,
     operands) = _spmv_setup(vals, cols, x, br)
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    y, nacc = pl.pallas_call(
        functools.partial(_spmv_kernel_rt, mode=mode, out_rows=out_rows),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="spmv_ell_rt",
    )(ns.k_operand(k), *operands)
    return y.reshape(R), nacc
