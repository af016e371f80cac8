"""Noise modes — the TPU/JAX vocabulary of the paper's noise language N.

The paper injects assembly patterns (fp_add64, l1_ld64, memory_ld64) into loop
bodies. On TPU the unit of overlap is not an OoO window but XLA's static
schedule of MXU / VPU / DMA / ICI; the noise quantum is one HLO op group
("pattern") rather than one instruction, because XLA schedules and fuses
whole ops, never single instructions. Each mode is:

  make_state(rng)        allocate DISJOINT noise buffers (semantics preserving
                         by construction — the paper's R_n ∩ R_s = ∅ argument)
  apply(state, k)        emit k patterns (k a static python int baked into
                         the trace: the unrolled HLO the payload census
                         counts); returns (aux, new_state).
                         ``aux`` is returned from the jitted step so XLA
                         cannot DCE the noise (the `volatile` analogue).
  apply_rt(state, k)     same patterns with k a RUNTIME operand (traced int32
                         scalar, bounded ``lax.fori_loop``) — one jitted
                         executable serves a whole k-sweep; every sweep
                         times this one. For k >= 1 the emitted arithmetic
                         matches ``apply`` pattern-for-pattern, so the census
                         counts what the sweep ran; only the k=0 aux differs
                         (sum of carried accumulators instead of literal 0).
  pattern_cost(hw)       per-pattern resource cost (FLOPs / HBM bytes / ICI
                         bytes / serial latency) — drives the analytic
                         saturation model in core/analytic.py.

Every pattern is emitted inside ``jax.named_scope(NOISE_SCOPE)`` so the HLO
metadata carries the tag; core/payload.py re-parses optimized HLO and counts
surviving payload ops (the paper's §2.3 static payload/overhead verification).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

NOISE_SCOPE = "noise_pattern"

# Independent accumulator chains, like the paper's fadd d31/d30/d29/d28 round
# robin — keeps noise throughput-bound instead of latency-bound.
N_CHAINS = 4


@dataclasses.dataclass(frozen=True)
class PatternCost:
    """Per-pattern resource footprint on the target hardware."""
    flops: float = 0.0          # FLOPs issued per pattern
    hbm_bytes: float = 0.0      # HBM traffic per pattern
    ici_bytes: float = 0.0      # per-chip ICI traffic per pattern
    serial_s: float = 0.0       # unavoidable serial latency per pattern
    vmem_bytes: float = 0.0     # VMEM-local traffic (not an HBM cost)

    def time_on(self, hw) -> dict[str, float]:
        """Seconds this pattern adds to each resource timeline of one chip."""
        return {
            "compute": self.flops / hw.peak_flops,
            "memory": self.hbm_bytes / hw.hbm_bw,
            "ici": self.ici_bytes / hw.ici_bw,
            "latency": self.serial_s,
        }


@dataclasses.dataclass(frozen=True)
class NoiseMode:
    name: str
    target: str                              # compute | memory | latency | ici | vmem
    make_state: Callable[[jax.Array], Any]   # rng -> state pytree
    apply: Callable[[Any, int], tuple[jax.Array, Any]]
    pattern_cost: Callable[[Any], PatternCost]
    # runtime-k variant: the one every sweep times
    apply_rt: Callable[[Any, jax.Array], tuple[jax.Array, Any]]
    description: str = ""


@dataclasses.dataclass(frozen=True)
class NoiseScale:
    """Buffer sizing. Tests shrink these; benchmarks enlarge them."""
    vpu_rows: int = 8              # VPU tile (rows, 128) ~ one vreg row group
    mxu_dim: int = 128             # MXU-aligned square matmul
    vmem_rows: int = 64            # small resident buffer (stays in VMEM/L1)
    hbm_mib: int = 64              # dedicated streaming buffer (>> LLC)
    hbm_tile_rows: int = 256       # rows of 128 f32 per streaming pattern
    chase_len: int = 1 << 22       # pointer-chase table entries (16 MiB)
    ici_kib: int = 256             # collective noise buffer per pattern


# ---------------------------------------------------------------------------
# Compute noise
# ---------------------------------------------------------------------------

def _fp_add_state(rng, sc: NoiseScale):
    c = jax.random.normal(rng, (sc.vpu_rows, 128), jnp.float32) * 1e-3
    accs = tuple(jnp.zeros((sc.vpu_rows, 128), jnp.float32) for _ in range(N_CHAINS))
    return {"c": c, "accs": accs}


def _fp_add_apply(state, k: int):
    accs = list(state["accs"])
    c = state["c"]
    with jax.named_scope(NOISE_SCOPE):
        for i in range(k):
            j = i % N_CHAINS
            accs[j] = accs[j] + c
    aux = sum(jnp.sum(a) for a in accs) if k else jnp.float32(0)
    return aux, dict(state, accs=tuple(accs))


def _fp_add_apply_rt(state, k):
    """Runtime-k twin of ``_fp_add_apply``: identical add order via a bounded
    fori_loop over a stacked accumulator (chain i % N_CHAINS gets pattern i)."""
    c = state["c"]
    accs = jnp.stack(state["accs"])
    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(
            0, k, lambda i, a: a.at[i % N_CHAINS].add(c), accs)
    aux = jnp.sum(accs)
    return aux, dict(state, accs=tuple(accs[j] for j in range(N_CHAINS)))


def _mxu_state(rng, sc: NoiseScale):
    d = sc.mxu_dim
    # c = identity: the chained product stays exactly bounded; XLA cannot
    # simplify (c is a runtime buffer, not a constant).
    return {"m": jax.random.normal(rng, (d, d), jnp.bfloat16),
            "c": jnp.eye(d, dtype=jnp.bfloat16)}


def _mxu_apply(state, k: int):
    m, c = state["m"], state["c"]
    with jax.named_scope(NOISE_SCOPE):
        for _ in range(k):
            m = jax.lax.dot(m, c, precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.bfloat16)
    return jnp.sum(m.astype(jnp.float32)), dict(state, m=m)


def _mxu_apply_rt(state, k):
    m, c = state["m"], state["c"]
    with jax.named_scope(NOISE_SCOPE):
        m = jax.lax.fori_loop(
            0, k,
            lambda i, mm: jax.lax.dot(mm, c, precision=jax.lax.Precision.DEFAULT,
                                      preferred_element_type=jnp.bfloat16),
            m)
    return jnp.sum(m.astype(jnp.float32)), dict(state, m=m)


# ---------------------------------------------------------------------------
# Data-access noise
# ---------------------------------------------------------------------------

def _vmem_state(rng, sc: NoiseScale):
    return {"buf": jax.random.normal(rng, (sc.vmem_rows, 128), jnp.float32),
            "accs": tuple(jnp.zeros((8, 128), jnp.float32) for _ in range(N_CHAINS))}


def _vmem_apply(state, k: int):
    """l1_ld analogue: k re-reads of a small resident buffer at rotating
    offsets (distinct slices defeat CSE; buffer never leaves VMEM/L1)."""
    buf = state["buf"]
    accs = list(state["accs"])
    rows = buf.shape[0]
    with jax.named_scope(NOISE_SCOPE):
        for i in range(k):
            off = (i * 13) % max(rows - 8, 1)
            accs[i % N_CHAINS] = accs[i % N_CHAINS] + jax.lax.dynamic_slice(
                buf, (off, 0), (8, 128))
    aux = sum(jnp.sum(a) for a in accs) if k else jnp.float32(0)
    return aux, dict(state, accs=tuple(accs))


def _vmem_apply_rt(state, k):
    buf = state["buf"]
    accs = jnp.stack(state["accs"])
    rows = buf.shape[0]
    mod = max(rows - 8, 1)

    def body(i, a):
        off = (i * 13) % mod
        return a.at[i % N_CHAINS].add(jax.lax.dynamic_slice(buf, (off, 0),
                                                            (8, 128)))

    with jax.named_scope(NOISE_SCOPE):
        accs = jax.lax.fori_loop(0, k, body, accs)
    aux = jnp.sum(accs)
    return aux, dict(state, accs=tuple(accs[j] for j in range(N_CHAINS)))


def _hbm_stream_state(rng, sc: NoiseScale):
    n_f32 = sc.hbm_mib * (1 << 20) // 4
    rows = n_f32 // 128
    return {"buf": jax.random.normal(rng, (rows, 128), jnp.float32),
            "acc": jnp.zeros((sc.hbm_tile_rows, 128), jnp.float32)}


def _hbm_stream_apply(state, k: int, tile_rows: int):
    """memory_ld (bandwidth flavour): k streaming reads of a TILE from a
    dedicated HBM buffer at stride-scattered offsets (defeats reuse)."""
    buf, acc = state["buf"], state["acc"]
    rows = buf.shape[0]
    n_tiles = max(rows // tile_rows, 1)
    with jax.named_scope(NOISE_SCOPE):
        for i in range(k):
            t = (i * 197) % n_tiles          # large co-prime stride: no reuse
            acc = acc + jax.lax.dynamic_slice(buf, (t * tile_rows, 0),
                                              (tile_rows, 128))
    return jnp.sum(acc), dict(state, acc=acc)


def _hbm_stream_apply_rt(state, k, tile_rows: int):
    buf, acc = state["buf"], state["acc"]
    rows = buf.shape[0]
    n_tiles = max(rows // tile_rows, 1)

    def body(i, a):
        t = (i * 197) % n_tiles
        return a + jax.lax.dynamic_slice(buf, (t * tile_rows, 0),
                                         (tile_rows, 128))

    with jax.named_scope(NOISE_SCOPE):
        acc = jax.lax.fori_loop(0, k, body, acc)
    return jnp.sum(acc), dict(state, acc=acc)


def _chase_state(rng, sc: NoiseScale):
    # A random single-cycle permutation: idx -> table[idx] visits every entry.
    n = sc.chase_len
    perm = np.random.RandomState(np.asarray(jax.random.key_data(rng))[-1] % (2**31)
                                 ).permutation(n).astype(np.int32)
    table = np.empty(n, np.int32)
    table[perm[:-1]] = perm[1:]
    table[perm[-1]] = perm[0]
    return {"table": jnp.asarray(table), "idx": jnp.int32(perm[0]),
            "acc": jnp.int32(0)}


def _chase_apply(state, k: int):
    """memory_ld (latency flavour): k serially dependent 1-element gathers —
    the paper's chaotic pointer chase. Dependency chain is the point."""
    table, idx, acc = state["table"], state["idx"], state["acc"]
    with jax.named_scope(NOISE_SCOPE):
        for _ in range(k):
            idx = table[idx]
            acc = acc + idx
    return acc, dict(state, idx=idx, acc=acc)


def _chase_apply_rt(state, k):
    table = state["table"]

    def body(_, carry):
        idx, acc = carry
        idx = table[idx]
        return idx, acc + idx

    with jax.named_scope(NOISE_SCOPE):
        idx, acc = jax.lax.fori_loop(0, k, body,
                                     (state["idx"], state["acc"]))
    return acc, dict(state, idx=idx, acc=acc)


# ---------------------------------------------------------------------------
# ICI collective noise (per mesh axis)
# ---------------------------------------------------------------------------

_shard_map = compat.shard_map


def _ici_state(rng, sc: NoiseScale):
    n = sc.ici_kib * 1024 // 4
    return {"v": jax.random.normal(rng, (n,), jnp.float32)}


def _mesh_for_collectives(mesh: Optional[Any], axis: str):
    """The mesh the collective runs over; an ICI mode without a mesh axis of
    its name raises — local work in its place would feed the ``ici`` node
    of the strategy tree with a compute measurement."""
    m = mesh if mesh is not None else compat.get_abstract_mesh()
    if m is None or axis not in m.axis_names:
        raise ValueError(
            f"ICI noise over mesh axis {axis!r} needs a mesh with that axis "
            f"(make_modes(mesh=...) or an enclosing compat.set_mesh); found "
            f"{'no mesh' if m is None else m.axis_names}")
    return m


def _ici_allreduce_apply(state, k: int, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    size = compat.mesh_axis_sizes(m)[axis]

    def body(x):
        with jax.named_scope(NOISE_SCOPE):
            for _ in range(k):
                x = jax.lax.psum(x, axis) * (1.0 / size)
        return x

    from jax.sharding import PartitionSpec as P
    out = _shard_map(body, m, P(), P())(v)
    return jnp.sum(out), dict(state, v=out)


def _ici_allreduce_apply_rt(state, k, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    size = compat.mesh_axis_sizes(m)[axis]

    def body(x, kk):   # kk replicated: runtime trip count inside the shard
        with jax.named_scope(NOISE_SCOPE):
            return jax.lax.fori_loop(
                0, kk, lambda _, xx: jax.lax.psum(xx, axis) * (1.0 / size), x)

    from jax.sharding import PartitionSpec as P
    out = _shard_map(body, m, (P(), P()), P())(v, jnp.asarray(k, jnp.int32))
    return jnp.sum(out), dict(state, v=out)


def _ici_allgather_apply(state, k: int, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    from jax.sharding import PartitionSpec as P

    def body(x):  # x: local shard (n/size,)
        with jax.named_scope(NOISE_SCOPE):
            for _ in range(k):
                # (size, n/size); keep one row: the chain stays one
                # collective per pattern with no local reduction
                x = jax.lax.all_gather(x, axis)[0]
        return x

    out = _shard_map(body, m, P(axis), P(axis))(v)
    return jnp.sum(out), dict(state, v=out)


def _ici_allgather_apply_rt(state, k, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    from jax.sharding import PartitionSpec as P

    def body(x, kk):

        def one(_, xx):
            return jax.lax.all_gather(xx, axis)[0]

        with jax.named_scope(NOISE_SCOPE):
            return jax.lax.fori_loop(0, kk, one, x)

    out = _shard_map(body, m, (P(axis), P()), P(axis))(
        v, jnp.asarray(k, jnp.int32))
    return jnp.sum(out), dict(state, v=out)


def _ici_a2a_apply(state, k: int, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    size = compat.mesh_axis_sizes(m)[axis]
    from jax.sharding import PartitionSpec as P

    def body(x):  # local shard (n/size,) -> reshape (size, chunk)
        chunk = x.shape[0] // size
        y = x[: size * chunk].reshape(size, chunk)
        with jax.named_scope(NOISE_SCOPE):
            for _ in range(k):
                y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0,
                                       tiled=False)
        return x.at[: size * chunk].set(y.reshape(-1))

    out = _shard_map(body, m, P(axis), P(axis))(v)
    return jnp.sum(out), dict(state, v=out)


def _ici_a2a_apply_rt(state, k, axis: str, mesh=None):
    v = state["v"]
    m = _mesh_for_collectives(mesh, axis)
    size = compat.mesh_axis_sizes(m)[axis]
    from jax.sharding import PartitionSpec as P

    def body(x, kk):
        chunk = x.shape[0] // size
        y = x[: size * chunk].reshape(size, chunk)

        def one(_, yy):
            return jax.lax.all_to_all(yy, axis, split_axis=0, concat_axis=0,
                                      tiled=False)

        with jax.named_scope(NOISE_SCOPE):
            y = jax.lax.fori_loop(0, kk, one, y)
        return x.at[: size * chunk].set(y.reshape(-1))

    out = _shard_map(body, m, (P(axis), P()), P(axis))(
        v, jnp.asarray(k, jnp.int32))
    return jnp.sum(out), dict(state, v=out)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def make_modes(scale: NoiseScale = NoiseScale(), *, mesh=None,
               ici_axis: str = "model") -> dict[str, NoiseMode]:
    """Instantiate the standard noise-mode registry at a given scale."""
    sc = scale

    def _c(**kw):
        return lambda hw: PatternCost(**kw)

    vpu_flops = sc.vpu_rows * 128
    mxu_flops = 2 * sc.mxu_dim ** 3
    tile_bytes = sc.hbm_tile_rows * 128 * 4
    ici_bytes = sc.ici_kib * 1024

    modes = {
        "fp_add32": NoiseMode(
            "fp_add32", "compute", partial(_fp_add_state, sc=sc), _fp_add_apply,
            _c(flops=vpu_flops), apply_rt=_fp_add_apply_rt,
            description="chained VPU vector adds on disjoint f32 tiles "
                        "(paper: fp_add64)"),
        "mxu_fma128": NoiseMode(
            "mxu_fma128", "compute", partial(_mxu_state, sc=sc), _mxu_apply,
            _c(flops=mxu_flops, vmem_bytes=2 * sc.mxu_dim ** 2),
            apply_rt=_mxu_apply_rt,
            description="chained 128x128 bf16 matmuls — stresses the MXU "
                        "systolic array"),
        "vmem_ld": NoiseMode(
            "vmem_ld", "vmem", partial(_vmem_state, sc=sc), _vmem_apply,
            _c(flops=8 * 128, vmem_bytes=8 * 128 * 4),
            apply_rt=_vmem_apply_rt,
            description="re-reads of a VMEM-resident tile (paper: l1_ld64)"),
        "hbm_stream": NoiseMode(
            "hbm_stream", "memory", partial(_hbm_stream_state, sc=sc),
            lambda s, k: _hbm_stream_apply(s, k, sc.hbm_tile_rows),
            _c(flops=tile_bytes / 4, hbm_bytes=tile_bytes),
            apply_rt=lambda s, k: _hbm_stream_apply_rt(s, k, sc.hbm_tile_rows),
            description="streaming tile reads from a dedicated HBM buffer "
                        "(bandwidth)"),
        "hbm_latency": NoiseMode(
            "hbm_latency", "latency", partial(_chase_state, sc=sc), _chase_apply,
            lambda hw: PatternCost(hbm_bytes=4.0, serial_s=hw.hbm_latency_s),
            apply_rt=_chase_apply_rt,
            description="serially dependent pointer chase (paper: memory_ld64 "
                        "chaotic)"),
        "ici_allreduce": NoiseMode(
            "ici_allreduce", "ici", partial(_ici_state, sc=sc),
            partial(_ici_allreduce_apply, axis=ici_axis, mesh=mesh),
            _c(ici_bytes=2 * ici_bytes),   # ring all-reduce ≈ 2(n-1)/n·B
            apply_rt=partial(_ici_allreduce_apply_rt, axis=ici_axis, mesh=mesh),
            description=f"chained psum over mesh axis {ici_axis!r} on a "
                        "disjoint buffer"),
        "ici_allgather": NoiseMode(
            "ici_allgather", "ici", partial(_ici_state, sc=sc),
            partial(_ici_allgather_apply, axis=ici_axis, mesh=mesh),
            _c(ici_bytes=ici_bytes),
            apply_rt=partial(_ici_allgather_apply_rt, axis=ici_axis, mesh=mesh),
            description=f"chained all-gather over mesh axis {ici_axis!r}"),
        "ici_a2a": NoiseMode(
            "ici_a2a", "ici", partial(_ici_state, sc=sc),
            partial(_ici_a2a_apply, axis=ici_axis, mesh=mesh),
            _c(ici_bytes=ici_bytes),
            apply_rt=partial(_ici_a2a_apply_rt, axis=ici_axis, mesh=mesh),
            description=f"chained all-to-all over mesh axis {ici_axis!r}"),
    }
    return modes


# Paper-facing aliases (AArch64 names -> TPU analogues), for the benchmarks.
PAPER_ALIASES = {
    "fp_add64": "fp_add32",
    "l1_ld64": "vmem_ld",
    "memory_ld64": "hbm_stream",
    "memory_chase": "hbm_latency",
}
