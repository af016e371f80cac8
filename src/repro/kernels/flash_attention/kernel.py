"""Flash attention (forward) for TPU: online-softmax blocked attention with
GQA, causal and sliding-window masks, and an instruction-level noise slot.

Grid (B*H, Sq/bq, Sk/bk), kv innermost. Blocks: q (1,bq,hd), k/v (1,bk,hd);
f32 running max / sum / accumulator live in VMEM scratch shaped (bq,128) /
(bq,128) / (bq,hd) (the 128-lane replication matches the official TPU flash
kernels — scalar-per-row state is stored broadcast along lanes).

Causal skip: kv blocks entirely above the diagonal are skipped (pl.when), so
compiled FLOPs stay ~S²/2 — visible in the roofline accounting. Sliding
window additionally skips blocks entirely below the window.

``flash_attention_pallas_rt`` is the compile-once twin: the noise quantity is
a scalar-prefetch int32 operand and patterns come from the bounded runtime-k
loop (noise_slots.emit_noise_rt) — one executable per (mode,) serves the
whole k-sweep, bitwise identical to the static path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels import noise_slots as ns

NEG_INF = -1e30


def _fa_body(q_ref, k_ref, v_ref, noise_ref, o_ref, nacc_ref,
             m_ref, l_ref, acc_ref, emit, *, scale: float, causal: bool,
             window: int, bq: int, bk: int):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ns.init_noise(nacc_ref, (bh == 0) & (qi == 0) & (ki == 0))

    q0 = qi * bq                      # first q position of this block
    k0 = ki * bk

    # block-level skip conditions (both resolve at run time on the grid ids)
    live = jnp.bool_(True)
    if causal:
        live &= k0 <= q0 + bq - 1               # not entirely above diagonal
    if window:
        live &= q0 - (k0 + bk - 1) < window     # not entirely out of window

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)  # (bq,bk)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            keep &= qpos >= kpos
        if window:
            keep &= qpos - kpos < window
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                            # (bq,1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                    # (bq,1)
        l_new = corr * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        emit(nacc_ref, noise_ref, bh * 131 + qi * 17 + ki)

    @pl.when(ki == nk - 1)
    def _():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _fa_kernel(q_ref, k_ref, v_ref, noise_ref, o_ref, nacc_ref,
               m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
               window: int, bq: int, bk: int, mode: str, k_noise: int):
    _fa_body(q_ref, k_ref, v_ref, noise_ref, o_ref, nacc_ref,
             m_ref, l_ref, acc_ref,
             lambda nacc, nz, step: ns.emit_noise(
                 mode, k_noise, nacc, nz, src_ref=None, step=step),
             scale=scale, causal=causal, window=window, bq=bq, bk=bk)


def _fa_kernel_rt(kq_ref, q_ref, k_ref, v_ref, noise_ref, o_ref, nacc_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                  window: int, bq: int, bk: int, mode: str):
    _fa_body(q_ref, k_ref, v_ref, noise_ref, o_ref, nacc_ref,
             m_ref, l_ref, acc_ref,
             lambda nacc, nz, step: ns.emit_noise_rt(
                 mode, kq_ref[0], nacc, nz, src_ref=None, step=step),
             scale=scale, causal=causal, window=window, bq=bq, bk=bk)


def _fa_setup(q, k, v, bq, bk):
    B, H, Sq, hd = q.shape
    _, KH, Sk, _ = k.shape
    assert H % KH == 0, (H, KH)
    G = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    grid = (B * H, Sq // bq, Sk // bk)
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * KH, Sk, hd)
    vf = v.reshape(B * KH, Sk, hd)

    def kv_idx(bh, qi, ki, *_):
        b = bh // H
        h = bh % H
        return (b * KH + h // G, ki, 0)

    in_specs = [
        pl.BlockSpec((1, bq, hd), lambda bh, qi, ki, *_: (bh, qi, 0)),
        pl.BlockSpec((1, bk, hd), kv_idx),
        pl.BlockSpec((1, bk, hd), kv_idx),
        ns.noise_in_spec(3),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, hd), lambda bh, qi, ki, *_: (bh, qi, 0)),
        ns.noise_out_spec(3),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        ns.noise_out_shape(),
    ]
    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),   # running max
        pltpu.VMEM((bq, 128), jnp.float32),   # running sum
        pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
    ]
    return (B, H, Sq, hd, bq, bk, grid, scale, (qf, kf, vf),
            in_specs, out_specs, out_shape, scratch)


def flash_attention_pallas(q, k, v, noise, *, causal: bool = True,
                           window: int = 0, bq: int = 128, bk: int = 128,
                           mode: str = "none", k_noise: int = 0,
                           interpret: bool = False):
    """q (B,H,Sq,hd); k,v (B,KH,Sk,hd) -> (out (B,H,Sq,hd), nacc (8,128))."""
    (B, H, Sq, hd, bq, bk, grid, scale, flat, in_specs, out_specs,
     out_shape, scratch) = _fa_setup(q, k, v, bq, bk)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, mode=mode,
                               k_noise=k_noise)
    out, nacc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention",
    )(*flat, noise)
    return out.reshape(B, H, Sq, hd), nacc


def flash_attention_pallas_rt(kq, q, k, v, noise, *, causal: bool = True,
                              window: int = 0, bq: int = 128, bk: int = 128,
                              mode: str = "fp", interpret: bool = False):
    """Runtime-k twin of ``flash_attention_pallas`` (``kq``: the traced
    noise quantity; named to avoid clashing with the key tensor ``k``)."""
    (B, H, Sq, hd, bq, bk, grid, scale, flat, in_specs, out_specs,
     out_shape, scratch) = _fa_setup(q, k, v, bq, bk)

    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out, nacc = pl.pallas_call(
        functools.partial(_fa_kernel_rt, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, mode=mode),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_attention_rt",
    )(ns.k_operand(kq), *flat, noise)
    return out.reshape(B, H, Sq, hd), nacc
