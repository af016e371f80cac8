"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
exact noise-payload accounting, and static-k vs runtime-k equivalence
(bitwise) for every kernel and noise mode."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.noise_probes import ops as probe_ops
from repro.kernels.noise_probes.ref import probe_ref
from repro.kernels.noise_slots import K_MAX
from repro.kernels.noisy_matmul import ops as mm_ops
from repro.kernels.noisy_matmul.ops import default_noise_operand
from repro.kernels.noisy_matmul.ref import fp_noise_ref, matmul_ref
from repro.kernels.spmv_ell import ops as spmv_ops
from repro.kernels.spmv_ell.ref import (fp_noise_ell_ref, make_band_ell,
                                        spmv_ell_ref, vmem_noise_ell_ref)

# every kernel here runs in the Pallas interpreter, by name
flash_attention = partial(fa_ops.flash_attention, backend="interpret")
flash_attention_rt = partial(fa_ops.flash_attention_rt, backend="interpret")
run_probe = partial(probe_ops.run_probe, backend="interpret")
run_probe_rt = partial(probe_ops.run_probe_rt, backend="interpret")
noisy_matmul = partial(mm_ops.noisy_matmul, backend="interpret")
noisy_matmul_rt = partial(mm_ops.noisy_matmul_rt, backend="interpret")
spmv_ell = partial(spmv_ops.spmv_ell, backend="interpret")
spmv_ell_rt = partial(spmv_ops.spmv_ell_rt, backend="interpret")


@pytest.mark.parametrize("M,N,K", [(256, 256, 256), (512, 256, 384),
                                   (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(M, N, K, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32).astype(dtype)
    out, _ = noisy_matmul(a, b, bm=128, bn=128, bk=128)
    ref = matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("mode,k", [("fp", 1), ("fp", 5), ("mxu", 2),
                                    ("vmem", 3)])
def test_matmul_noise_does_not_change_result(mode, k):
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    clean, _ = noisy_matmul(a, b, bm=128, bn=128, bk=128)
    noisy, nacc = noisy_matmul(a, b, mode=mode, k_noise=k,
                               bm=128, bn=128, bk=128)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(noisy))
    assert np.abs(np.asarray(nacc)).sum() > 0     # payload executed


def test_matmul_fp_noise_exact():
    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 256), jnp.float32)
    noise = default_noise_operand()
    _, nacc = noisy_matmul(a, b, noise, mode="fp", k_noise=3,
                           bm=128, bn=128, bk=128)
    n_steps = 2 * 2 * 2
    np.testing.assert_allclose(np.asarray(nacc),
                               np.asarray(fp_noise_ref(noise, 3, n_steps)),
                               rtol=1e-5)


@pytest.mark.parametrize("H,KH,Sq,Sk,hd,causal,window", [
    (4, 4, 256, 256, 64, True, 0),
    (8, 2, 256, 256, 64, True, 0),      # GQA
    (4, 1, 128, 128, 128, True, 0),     # MQA
    (4, 4, 128, 128, 64, False, 0),     # bidirectional (encoder)
    (4, 2, 256, 256, 64, True, 64),     # sliding window
])
def test_flash_attention_sweep(H, KH, Sq, Sk, hd, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, H, Sq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (2, KH, Sk, hd), jnp.float32)
    v = jax.random.normal(ks[2], (2, KH, Sk, hd), jnp.float32)
    out, _ = flash_attention(q, k, v, causal=causal, window=window,
                             bq=128, bk=128)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 4, 128, 64), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 4, 128, 64), jnp.float32).astype(jnp.bfloat16)
    out, _ = flash_attention(q, k, v, bq=64, bk=64)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2,
                               atol=3e-2)


@pytest.mark.parametrize("n,L,q", [(512, 128, 0.0), (1024, 128, 0.5),
                                   (512, 256, 1.0)])
def test_spmv_sweep(n, L, q):
    vals, cols = make_band_ell(n, L, q, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    y, _ = spmv_ell(vals, cols, x, br=128)
    ref = spmv_ell_ref(vals, cols, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("mode", ["fp", "mxu", "vmem"])
@pytest.mark.parametrize("k,n_steps", [(1, 4), (3, 16)])
def test_probe_exact(mode, k, n_steps):
    got = run_probe(mode=mode, k_noise=k, n_steps=n_steps)
    want = probe_ref(default_noise_operand(), mode=mode, k_noise=k,
                     n_steps=n_steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# runtime-k protocol: for every kernel and mode, the scalar-prefetch path
# must be BITWISE identical to the static-k path (same pattern arithmetic in
# the same order), including k=0, so compile-once sweeps measure the same
# injected work as the paper's trace-per-k cost model.
# ---------------------------------------------------------------------------

def _assert_pair_equal(static_out, rt_out):
    for s, r in zip(static_out, rt_out):
        np.testing.assert_array_equal(np.asarray(s), np.asarray(r))


@pytest.mark.parametrize("mode", ["fp", "mxu", "vmem"])
@pytest.mark.parametrize("k", [0, 1, 5])
def test_matmul_runtime_k_matches_static(mode, k):
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    _assert_pair_equal(
        noisy_matmul(a, b, mode=mode, k_noise=k, bm=128, bn=128, bk=128),
        noisy_matmul_rt(jnp.int32(k), a, b, mode=mode,
                        bm=128, bn=128, bk=128))


@pytest.mark.parametrize("mode", ["fp", "vmem"])
@pytest.mark.parametrize("n,L,k", [(512, 16, 1), (512, 16, 5), (256, 128, 3)])
def test_spmv_runtime_k_matches_static(mode, n, L, k):
    vals, cols = make_band_ell(n, L, 0.5, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    _assert_pair_equal(
        spmv_ell(vals, cols, x, br=128, mode=mode, k_noise=k),
        spmv_ell_rt(jnp.int32(k), vals, cols, x, br=128, mode=mode))


@pytest.mark.parametrize("mode", ["fp", "mxu", "vmem"])
@pytest.mark.parametrize("k", [1, 4])
def test_attention_runtime_k_matches_static(mode, k):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.float32)
    kk = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
    _assert_pair_equal(
        flash_attention(q, kk, v, mode=mode, k_noise=k, bq=64, bk=64),
        flash_attention_rt(jnp.int32(k), q, kk, v, mode=mode, bq=64, bk=64))


@pytest.mark.parametrize("mode", ["fp", "mxu", "vmem"])
@pytest.mark.parametrize("k", [0, 1, 3])
def test_probe_runtime_k_matches_static(mode, k):
    np.testing.assert_array_equal(
        np.asarray(run_probe(mode=mode, k_noise=k, n_steps=8)),
        np.asarray(run_probe_rt(jnp.int32(k), mode=mode, n_steps=8)))


def test_runtime_k_clamps_at_k_max():
    """The bounded fori_loop: k > K_MAX emits exactly K_MAX patterns."""
    got = run_probe_rt(jnp.int32(K_MAX + 7), mode="fp", n_steps=2)
    want = run_probe(mode="fp", k_noise=K_MAX, n_steps=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# spmv fp payload integrity: the addend derives from a RUNTIME block of
# vals (a compile-time constant could be strength-reduced to nacc += k*c,
# deleting the payload), and the exact oracle still holds.
# ---------------------------------------------------------------------------

def test_spmv_fp_noise_exact_and_data_dependent():
    vals, cols = make_band_ell(512, 16, 0.25, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (512,), jnp.float32)
    k = 4
    _, nacc = spmv_ell(vals, cols, x, br=128, mode="fp", k_noise=k)
    np.testing.assert_allclose(np.asarray(nacc),
                               np.asarray(fp_noise_ell_ref(vals, k, 128)),
                               rtol=1e-5, atol=1e-6)
    # the addend is data, not a constant: scaling vals scales nacc linearly
    _, nacc2 = spmv_ell(vals * 2.0, cols, x, br=128, mode="fp", k_noise=k)
    np.testing.assert_allclose(np.asarray(nacc2), 2.0 * np.asarray(nacc),
                               rtol=1e-5, atol=1e-6)


def test_spmv_vmem_noise_exact_narrow_block():
    """vmem patterns on a narrow ELL block (L < 128) add into the first L
    lanes only; the exact oracle pins offsets and widths."""
    vals, cols = make_band_ell(512, 16, 0.0, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (512,), jnp.float32)
    _, nacc = spmv_ell(vals, cols, x, br=128, mode="vmem", k_noise=3)
    nacc = np.asarray(nacc)
    np.testing.assert_allclose(nacc,
                               np.asarray(vmem_noise_ell_ref(vals, 3, 128)),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(nacc[:, :16]).sum() > 0
    np.testing.assert_array_equal(nacc[:, 16:], 0.0)


# Witnesses for the host nacc oracles: the same float32 loops as eager jnp,
# one device op per block and pattern.
def _fp_noise_ell_eager(vals, k_noise, br):
    R = vals.shape[0]
    br = min(br, R)
    c = sum(vals[i * br:i * br + 8, 0:1].astype(jnp.float32)
            for i in range(R // br))
    return k_noise * jnp.broadcast_to(c, (8, 128))


def _vmem_noise_ell_eager(vals, k_noise, br):
    R, L = vals.shape
    br = min(br, R)
    w = min(L, 128)
    acc = jnp.zeros((8, 128), jnp.float32)
    for i in range(R // br):
        blk = vals[i * br:(i + 1) * br].astype(jnp.float32)
        for j in range(k_noise):
            off = (i * 7 + j * 13) % max(br - 8, 1)
            acc = acc.at[:, 0:w].add(blk[off:off + 8, 0:w])
    return acc


@pytest.mark.parametrize("mode", ["fp", "vmem"])
@pytest.mark.parametrize("n,k,q,br", [(512, 0, 0.0, 128), (512, 3, 0.25, 128),
                                      (256, 5, 1.0, 512),   # br > rows
                                      (32768, 16, 1.0, 128)])
def test_spmv_noise_oracles_match_eager_bitwise(mode, n, k, q, br):
    """The NumPy nacc oracles equal the eager jnp loops bit for bit, up to
    the benchmark's 2^15 rows at k = 16, and return host float32 (8, 128)."""
    vals, _ = make_band_ell(n, 16, q, seed=n + k)
    host, eager = {"fp": (fp_noise_ell_ref, _fp_noise_ell_eager),
                   "vmem": (vmem_noise_ell_ref, _vmem_noise_ell_eager)}[mode]
    got = host(vals, k, br)
    assert isinstance(got, np.ndarray)
    assert got.shape == (8, 128) and got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(eager(vals, k, br)))
