"""Loop-body noise emitters — the direct analogue of the paper's LLVM pass.

The paper injects assembly patterns INTO the target loop body so the CPU's
out-of-order engine can overlap them with the original instructions. The JAX
analogue: kernels written as ``lax.fori_loop``/``lax.scan`` expose a *noise
slot* in their body; the emitters below generate k patterns there. XLA:CPU
compiles the body to one machine loop, so the host's real OoO engine performs
the absorption — measured host signatures are genuine, not simulated
(validated: a memory-bound triad absorbs 64+ fp patterns, a compute-bound FMA
chain saturates from k≈8).

Protocol (mirrors core.noise graph-level modes, but loop-carried):

  init(rng)               -> carry pytree of small noise buffers (disjoint
                             from kernel state: the paper's R_n ∩ R_s = ∅)
  emit(carry, k, i)       -> new carry, after issuing k patterns; ``i`` is the
                             loop induction variable (varies offsets so the
                             compiler cannot hoist or CSE patterns); k is a
                             static python int baked into the trace
  emit_rt(carry, k, i)    -> same patterns with k a RUNTIME operand (traced
                             int32, inner bounded ``lax.fori_loop``): one
                             jitted executable serves the whole k-sweep,
                             and every sweep times it
  finalize(carry)         -> scalar aux (returned from the jitted function —
                             the `volatile` analogue: DCE-proof)

Every pattern is emitted under ``named_scope(NOISE_SCOPE)`` so payload
verification (core.payload) can count surviving ops in optimized HLO.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.noise import NOISE_SCOPE, N_CHAINS

VEC = 8  # noise vector width (one AVX2 f32 register / one VPU sublane group)


@dataclasses.dataclass(frozen=True)
class LoopNoise:
    name: str
    target: str                       # compute | l1 | memory | latency
    init: Callable[[jax.Array], Any]
    emit: Callable[[Any, int, jax.Array], Any]
    finalize: Callable[[Any], jax.Array]
    payload_op: str = "add"           # dominant HLO opcode of one pattern
    # runtime-k emitter: the one every sweep times (a required keyword)
    emit_rt: Callable[[Any, jax.Array, jax.Array], Any] = \
        dataclasses.field(kw_only=True)
    description: str = ""


# ---------------------------------------------------------------------------
# fp_add — chained vector adds, round-robin over N_CHAINS accumulators
# (paper Fig. 1a: fadd d31/d30/d29/d28)
# ---------------------------------------------------------------------------

def _fp_init(rng):
    c = jax.random.normal(rng, (VEC,), jnp.float32) * 1e-6
    return {"c": c, "accs": tuple(jnp.zeros((VEC,), jnp.float32)
                                  for _ in range(N_CHAINS))}


def _fp_emit(carry, k, i):
    del i
    accs = list(carry["accs"])
    with jax.named_scope(NOISE_SCOPE):
        for j in range(k):
            accs[j % N_CHAINS] = accs[j % N_CHAINS] + carry["c"]
    return dict(carry, accs=tuple(accs))


def _fp_finalize(carry):
    return sum(jnp.sum(a) for a in carry["accs"])


def _unstack(accs):
    return tuple(accs[j] for j in range(N_CHAINS))


def _fp_emit_rt(carry, k, i):
    del i
    c = carry["c"]
    accs = jnp.stack(carry["accs"])
    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(
            0, k, lambda j, a: a.at[j % N_CHAINS].add(c), accs)
    return dict(carry, accs=_unstack(accs))


# ---------------------------------------------------------------------------
# fp_fma — multiply-add patterns (denser issue on FMA ports than plain add)
# ---------------------------------------------------------------------------

def _fma_emit(carry, k, i):
    del i
    accs = list(carry["accs"])
    c = carry["c"]
    with jax.named_scope(NOISE_SCOPE):
        for j in range(k):
            accs[j % N_CHAINS] = accs[j % N_CHAINS] * 0.999999 + c
    return dict(carry, accs=tuple(accs))


def _fma_emit_rt(carry, k, i):
    del i
    c = carry["c"]
    accs = jnp.stack(carry["accs"])

    def one(j, a):
        return a.at[j % N_CHAINS].set(a[j % N_CHAINS] * 0.999999 + c)

    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(0, k, one, accs)
    return dict(carry, accs=_unstack(accs))


# ---------------------------------------------------------------------------
# l1_ld — reads of a small cache-resident buffer at rotating offsets
# (paper Fig. 1c: l1_ld64)
# ---------------------------------------------------------------------------

L1_ROWS = 512  # 512*8*4B = 16 KiB: comfortably L1-resident


def _l1_init(rng):
    return {"buf": jax.random.normal(rng, (L1_ROWS, VEC), jnp.float32),
            "accs": tuple(jnp.zeros((VEC,), jnp.float32)
                          for _ in range(N_CHAINS))}


def _l1_emit(carry, k, i):
    buf = carry["buf"]
    accs = list(carry["accs"])
    with jax.named_scope(NOISE_SCOPE):
        for j in range(k):
            # offset varies with the induction variable AND the pattern index:
            # not hoistable, not CSE-able, still always an L1 hit.
            off = (i * 7 + j * 13) % L1_ROWS
            row = jax.lax.dynamic_slice(buf, (off, 0), (1, VEC))[0]
            accs[j % N_CHAINS] = accs[j % N_CHAINS] + row
    return dict(carry, accs=tuple(accs))


def _l1_emit_rt(carry, k, i):
    buf = carry["buf"]
    accs = jnp.stack(carry["accs"])

    def one(j, a):
        off = (i * 7 + j * 13) % L1_ROWS
        row = jax.lax.dynamic_slice(buf, (off, 0), (1, VEC))[0]
        return a.at[j % N_CHAINS].add(row)

    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(0, k, one, accs)
    return dict(carry, accs=_unstack(accs))


# ---------------------------------------------------------------------------
# mem_ld — strided reads of a dedicated buffer far larger than LLC
# (paper: memory_ld64, bandwidth flavour)
# ---------------------------------------------------------------------------

MEM_ROWS = 1 << 21  # 2M rows * 32B = 64 MiB >> LLC


def _mem_init(rng):
    del rng  # too big to fill with normals; iota is fine (never a constant)
    buf = (jnp.arange(MEM_ROWS * VEC, dtype=jnp.float32)
           .reshape(MEM_ROWS, VEC) * 1e-9)
    return {"buf": buf, "accs": tuple(jnp.zeros((VEC,), jnp.float32)
                                      for _ in range(N_CHAINS))}


def _mem_emit(carry, k, i):
    buf = carry["buf"]
    accs = list(carry["accs"])
    with jax.named_scope(NOISE_SCOPE):
        for j in range(k):
            # large co-prime stride: each pattern touches a fresh cache line
            # region; hardware prefetch gets no simple stream.
            off = ((i * (k or 1) + j) * 40_503) % MEM_ROWS
            row = jax.lax.dynamic_slice(buf, (off, 0), (1, VEC))[0]
            accs[j % N_CHAINS] = accs[j % N_CHAINS] + row
    return dict(carry, accs=tuple(accs))


def _mem_emit_rt(carry, k, i):
    buf = carry["buf"]
    accs = jnp.stack(carry["accs"])
    k_eff = jnp.maximum(k, 1)   # traced analogue of (k or 1)

    def one(j, a):
        off = ((i * k_eff + j) * 40_503) % MEM_ROWS
        row = jax.lax.dynamic_slice(buf, (off, 0), (1, VEC))[0]
        return a.at[j % N_CHAINS].add(row)

    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(0, k, one, accs)
    return dict(carry, accs=_unstack(accs))


# ---------------------------------------------------------------------------
# chase — serially dependent loads (paper: memory_ld64 latency flavour /
# lat_mem_rd's own access pattern). The dependency chain is the point.
# ---------------------------------------------------------------------------

CHASE_LEN = 1 << 20  # 4 MiB of int32 — larger than L2


def _chase_init(rng):
    seed = int(np.asarray(jax.random.key_data(rng)).ravel()[-1]) % (2**31)
    perm = np.random.RandomState(seed).permutation(CHASE_LEN).astype(np.int32)
    table = np.empty(CHASE_LEN, np.int32)
    table[perm[:-1]] = perm[1:]
    table[perm[-1]] = perm[0]
    return {"table": jnp.asarray(table), "idx": jnp.int32(int(perm[0]))}


def _chase_emit(carry, k, i):
    del i
    table, idx = carry["table"], carry["idx"]
    with jax.named_scope(NOISE_SCOPE):
        for _ in range(k):
            idx = jax.lax.dynamic_slice(table, (idx,), (1,))[0]
    return dict(carry, idx=idx)


def _chase_emit_rt(carry, k, i):
    del i
    table = carry["table"]

    def one(_, idx):
        return jax.lax.dynamic_slice(table, (idx,), (1,))[0]

    with jax.named_scope(NOISE_SCOPE):
        idx = jax.lax.fori_loop(0, k, one, carry["idx"])
    return dict(carry, idx=idx)


def _chase_finalize(carry):
    return carry["idx"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def make_loop_modes() -> dict[str, LoopNoise]:
    return {
        "fp_add": LoopNoise(
            "fp_add", "compute", _fp_init, _fp_emit, _fp_finalize, "add",
            emit_rt=_fp_emit_rt,
            description="round-robin chained vector adds (paper: fp_add64)"),
        "fp_fma": LoopNoise(
            "fp_fma", "compute", _fp_init, _fma_emit, _fp_finalize, "add",
            emit_rt=_fma_emit_rt,
            description="round-robin chained FMAs — saturates FMA ports faster"),
        "l1_ld": LoopNoise(
            "l1_ld", "l1", _l1_init, _l1_emit, _fp_finalize, "dynamic-slice",
            emit_rt=_l1_emit_rt,
            description="rotating reads of a 16 KiB resident buffer "
                        "(paper: l1_ld64)"),
        "mem_ld": LoopNoise(
            "mem_ld", "memory", _mem_init, _mem_emit, _fp_finalize,
            "dynamic-slice", emit_rt=_mem_emit_rt,
            description="strided reads of a 64 MiB buffer (paper: memory_ld64)"),
        "chase": LoopNoise(
            "chase", "latency", _chase_init, _chase_emit, _chase_finalize,
            "dynamic-slice", emit_rt=_chase_emit_rt,
            description="serially dependent pointer chase (latency probe)"),
    }


# Paper-facing aliases.
PAPER_LOOP_ALIASES = {
    "fp_add64": "fp_add",
    "l1_ld64": "l1_ld",
    "memory_ld64": "mem_ld",
}


def noisy_loop(body, n_iter, init_carry, noise: LoopNoise, k: int, rng=None):
    """Run ``body(i, carry) -> carry`` for ``n_iter`` iterations with ``k``
    noise patterns of ``noise`` emitted per iteration.

    Returns (final_carry, noise_aux). This is the generic injection site used
    by the bench ports; kernels with custom structure call ``noise.emit``
    directly in their own loop bodies.
    """
    rng = jax.random.PRNGKey(0) if rng is None else rng
    nc0 = noise.init(rng)

    def full_body(i, state):
        carry, nc = state
        carry = body(i, carry)
        nc = noise.emit(nc, k, i)
        return carry, nc

    carry, nc = jax.lax.fori_loop(0, n_iter, full_body, (init_carry, nc0))
    return carry, noise.finalize(nc)
