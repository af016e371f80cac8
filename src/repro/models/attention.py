"""Attention: MHA/GQA/MQA with RoPE, sliding windows, KV caches (full + ring),
cross-attention, and q-block-chunked scores (bounded memory at 32k context —
the XLA-level analogue of flash attention; the Pallas kernel in
repro.kernels.flash_attention is the TPU-optimized path).

Cache layout: k, v are (B, Kh, S, hd). Ring caches (sliding window) add
``kpos`` (S,) holding the absolute position stored in each slot (-1 = empty).

Paged layout (serving): one pool of fixed-size KV pages shared by every slot
— ``kp``/``vp`` are (P, Kh, page, hd) — plus a per-slot int32 page table
(B, max_pages) mapping logical page j of slot b to a pool page id. Logical
position t of slot b lives at pool[table[b, t // page], :, t % page]. Every
table entry must be a valid pool index; the serving engine points unassigned
entries at a dedicated trash page, so the attention code needs no sentinel
handling. Writes land on pages owned by exactly one slot (or the trash
page, which is never read), and reads gather a slot's pages in logical
order — so the paged softmax sees the same keys, in the same order, as the
dense (B, Kh, S, hd) layout and the two are numerically identical.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import _normal, apply_rope, cdtype_of, dtype_of, rope_angles
from repro.parallel.sharding import constrain

NEG_INF = -1e30


def init_attention(key, cfg):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    return {
        "wq": _normal(k1, (d, h, hd), d ** -0.5, dt),
        "wk": _normal(k2, (d, kh, hd), d ** -0.5, dt),
        "wv": _normal(k3, (d, kh, hd), d ** -0.5, dt),
        "wo": _normal(k4, (h, hd, d), (h * hd) ** -0.5, dt),
    }


def spec_attention():
    return {
        "wq": ("fsdp", "heads", None),
        "wk": ("fsdp", "kv_heads", None),
        "wv": ("fsdp", "kv_heads", None),
        "wo": ("heads", None, "fsdp"),
    }


def _project_qkv(p, cfg, x, positions):
    """x (B,S,D) -> q (B,H,S,hd) roped, k/v (B,Kh,S,hd) roped."""
    cd = cdtype_of(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta,
                           cfg.rope_scaling)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q.transpose(0, 2, 1, 3), "batch", "heads", "seq", None)
    k = constrain(k.transpose(0, 2, 1, 3), "batch", "kv_heads", "seq", None)
    v = constrain(v.transpose(0, 2, 1, 3), "batch", "kv_heads", "seq", None)
    return q, k, v


def _repeat_kv(cfg, k):
    if cfg.n_heads == cfg.n_kv_heads:
        return k
    return jnp.repeat(k, cfg.n_heads // cfg.n_kv_heads, axis=1)


def _sdpa_blocked(cfg, q, k, v, mask_fn, q_positions, q_block):
    """Blocked-over-queries softmax attention.

    q (B,H,Sq,hd); k,v (B,H,Sk,hd); mask_fn(qpos (Qb,), kidx (Sk,)) -> (Qb,Sk)
    bool keep-mask. Memory peak is O(Qb * Sk) scores instead of O(Sq * Sk).
    """
    B, H, Sq, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kidx = jnp.arange(k.shape[2], dtype=jnp.int32)

    def block(carry, inp):
        qb, qpos = inp  # (B,H,Qb,hd), (Qb,)
        s = jnp.einsum("bhqk,bhtk->bhqt", qb.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        keep = mask_fn(qpos, kidx)  # (Qb, Sk)
        s = jnp.where(keep[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        ob = jnp.einsum("bhqt,bhtk->bhqk", w, v.astype(jnp.float32))
        return carry, ob.astype(q.dtype)

    if Sq <= q_block:
        _, out = block(None, (q, q_positions))
        return out
    if Sq % q_block:  # non-divisible (e.g. VLM img+text): largest divisor
        q_block = next(d for d in range(q_block, 0, -1) if Sq % d == 0)
    nb = Sq // q_block
    qs = q.reshape(B, H, nb, q_block, hd).transpose(2, 0, 1, 3, 4)
    ps = q_positions.reshape(nb, q_block)
    _, out = jax.lax.scan(block, None, (qs, ps))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, hd)


def _flash_blocks(S, q_block, kv_block, causal, window):
    """Static per-q-block kv ranges (the triangular/window pruning)."""
    q_block = min(q_block, S)
    if S % q_block:
        q_block = next(d for d in range(q_block, 0, -1) if S % d == 0)
    kv_block = min(kv_block, S)
    if S % kv_block:
        kv_block = next(d for d in range(kv_block, 0, -1) if S % d == 0)
    ranges = []
    for qi in range(S // q_block):
        q0 = qi * q_block
        lo = max(0, (q0 - window + 1)) // kv_block if window else 0
        hi = ((q0 + q_block - 1) // kv_block + 1) if causal \
            else S // kv_block
        ranges.append((q0, lo, hi))
    return q_block, kv_block, ranges


def _tile_mask(q0, k0, q_block, kv_block, causal, window):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
    keep = jnp.ones((q_block, kv_block), bool)
    if causal:
        keep &= qpos >= kpos
    if window:
        keep &= qpos - kpos < window
    return keep


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _sdpa_flash_core(q, k, v, causal, window, q_block, kv_block):
    out, _, _ = _flash_fwd_impl(q, k, v, causal, window, q_block, kv_block)
    return out


def _flash_fwd_impl(q, k, v, causal, window, q_block, kv_block):
    """Online-softmax forward with STATIC triangular / window pruning.

    Per q block, only kv blocks inside the causal prefix (and window) are
    visited via a lax.scan with a static trip count — the pruning shows up
    in compiled FLOPs, not just at run time. Peak score memory is one
    (q_block, kv_block) tile. Returns (out, m, l) for the flash backward.
    """
    B, H, S, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    q_block, kv_block, ranges = _flash_blocks(S, q_block, kv_block, causal,
                                              window)
    kv_all = k.shape[2] // kv_block
    kb = k.reshape(B, H, kv_all, kv_block, hd).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, kv_all, kv_block, hd).transpose(2, 0, 1, 3, 4)

    outs, ms, ls = [], [], []
    for q0, lo, hi in ranges:
        qb = q[:, :, q0:q0 + q_block].astype(jnp.float32) * scale

        def body(carry, kv, q0=q0, lo=lo, qb=qb):
            m, l, acc, ki = carry
            kt, vt = kv                                   # (B,H,bk,hd)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kt.astype(jnp.float32))
            keep = _tile_mask(q0, (lo + ki) * kv_block, q_block, kv_block,
                              causal, window)
            s = jnp.where(keep[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", p,
                                          vt.astype(jnp.float32))
            return (m_new, l, acc, ki + 1), None

        m0 = jnp.full((B, H, q_block, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block, 1), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            body, (m0, l0, a0, jnp.int32(0)), (kb[lo:hi], vb[lo:hi]),
            length=hi - lo)
        outs.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
        ms.append(m)
        ls.append(l)
    return (jnp.concatenate(outs, axis=2), jnp.concatenate(ms, axis=2),
            jnp.concatenate(ls, axis=2))


def _flash_fwd(q, k, v, causal, window, q_block, kv_block):
    out, m, l = _flash_fwd_impl(q, k, v, causal, window, q_block, kv_block)
    return out, (q, k, v, out, m, l)


def _flash_bwd(causal, window, q_block, kv_block, res, do):
    """Flash backward: recompute each tile from the saved (m, l) row stats —
    no per-tile residuals survive the forward, so train-time activation
    memory stays O(S·hd) instead of O(S²) (llava temp: 102 GiB -> see
    EXPERIMENTS.md §Perf)."""
    q, k, v, out, m, l = res
    B, H, S, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    q_block, kv_block, ranges = _flash_blocks(S, q_block, kv_block, causal,
                                              window)
    kv_all = k.shape[2] // kv_block
    kb = k.reshape(B, H, kv_all, kv_block, hd).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, kv_all, kv_block, hd).transpose(2, 0, 1, 3, 4)

    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1, keepdims=True)

    dq_blocks = []
    dk = jnp.zeros((B, H, k.shape[2], hd), jnp.float32)
    dv = jnp.zeros_like(dk)
    for q0, lo, hi in ranges:
        qb = q[:, :, q0:q0 + q_block].astype(jnp.float32) * scale
        mb = m[:, :, q0:q0 + q_block]
        lb = jnp.maximum(l[:, :, q0:q0 + q_block], 1e-30)
        dob = dof[:, :, q0:q0 + q_block]
        db = delta[:, :, q0:q0 + q_block]

        def body(carry, kv, q0=q0, lo=lo, qb=qb, mb=mb, lb=lb, dob=dob,
                 db=db):
            dqb, dk, dv, ki = carry
            kt, vt = kv
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kt.astype(jnp.float32))
            keep = _tile_mask(q0, (lo + ki) * kv_block, q_block, kv_block,
                              causal, window)
            s = jnp.where(keep[None, None], s, NEG_INF)
            p = jnp.exp(s - mb) / lb                       # (B,H,bq,bk)
            dv_t = jnp.einsum("bhqk,bhqd->bhkd", p, dob)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vt.astype(jnp.float32))
            ds = p * (dp - db)                             # d(scaled scores)
            dqb = dqb + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                   kt.astype(jnp.float32)) * scale
            dk_t = jnp.einsum("bhqk,bhqd->bhkd", ds, qb) * 1.0
            off = (lo + ki) * kv_block
            dk = jax.lax.dynamic_update_slice(
                dk, jax.lax.dynamic_slice(
                    dk, (0, 0, off, 0), (B, H, kv_block, hd)) + dk_t,
                (0, 0, off, 0))
            dv = jax.lax.dynamic_update_slice(
                dv, jax.lax.dynamic_slice(
                    dv, (0, 0, off, 0), (B, H, kv_block, hd)) + dv_t,
                (0, 0, off, 0))
            return (dqb, dk, dv, ki + 1), None

        dq0 = jnp.zeros((B, H, q_block, hd), jnp.float32)
        (dqb, dk, dv, _), _ = jax.lax.scan(
            body, (dq0, dk, dv, jnp.int32(0)), (kb[lo:hi], vb[lo:hi]),
            length=hi - lo)
        dq_blocks.append(dqb)
    dq = jnp.concatenate(dq_blocks, axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_sdpa_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _sdpa_flash(cfg, q, k, v, positions, *, causal, window, q_block=1024,
                kv_block=1024):
    """XLA-level flash attention (custom VJP) — §Perf beyond-paper lever."""
    del cfg, positions  # positions are arange(S) on the train/prefill path
    return _sdpa_flash_core(q, k, v, causal, window, q_block, kv_block)


def _out_proj(p, cfg, attn_out):
    """attn_out (B,H,S,hd) -> (B,S,D)."""
    cd = cdtype_of(cfg)
    y = jnp.einsum("bhsk,hkd->bsd", attn_out, p["wo"].astype(cd))
    return constrain(y, "batch", "seq", "d_model")


def attn_train(p, cfg, x, positions, *, causal=True, window=0,
               return_cache=False, q_block=1024):
    """Full-sequence self-attention (train / prefill).

    positions: (S,) int32 absolute positions. window>0 = sliding window.
    cfg.attn_impl selects the score path: "blocked" (q-chunked, materializes
    (q_block, Sk) scores) or "flash" (online softmax + static pruning).
    """
    q, k, v = _project_qkv(p, cfg, x, positions)
    kf, vf = _repeat_kv(cfg, k), _repeat_kv(cfg, v)

    if getattr(cfg, "attn_impl", "blocked") == "flash":
        out = _sdpa_flash(cfg, q, kf, vf, positions, causal=causal,
                          window=window, q_block=q_block)
    else:
        def mask_fn(qpos, kidx):
            kpos = positions[kidx]
            keep = jnp.ones((qpos.shape[0], kidx.shape[0]), bool)
            if causal:
                keep &= qpos[:, None] >= kpos[None, :]
            if window:
                keep &= qpos[:, None] - kpos[None, :] < window
            return keep

        out = _sdpa_blocked(cfg, q, kf, vf, mask_fn, positions, q_block)
    y = _out_proj(p, cfg, out)
    if return_cache:
        return y, {"k": k, "v": v}
    return y


def init_cache(cfg, batch, max_seq, *, window=None):
    """Allocate a decode cache. For SWA the cache is a ring of size window."""
    w = cfg.window if window is None else window
    S = min(max_seq, w) if w else max_seq
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    z = jnp.zeros((batch, kh, S, hd), cdtype_of(cfg))
    cache = {"k": z, "v": z}
    if w:
        cache["kpos"] = jnp.full((S,), -1, jnp.int32)
    return cache


def cache_logical(*, paged=False):
    if paged:
        return {"kp": ("cache_pages", "cache_kv_heads", None, None),
                "vp": ("cache_pages", "cache_kv_heads", None, None)}
    return {"k": ("cache_batch", "cache_kv_heads", "cache_seq", None),
            "v": ("cache_batch", "cache_kv_heads", "cache_seq", None)}


def init_paged_cache(cfg, n_pages, page_size):
    """Allocate the shared KV page pool: {"kp","vp"} (P, Kh, page, hd).

    No batch dimension — slots share the pool through a page table (see the
    module docstring). Paged caches support full attention only (window=0);
    a ring would need per-slot wrap bookkeeping the table doesn't carry.
    """
    if cfg.window:
        raise NotImplementedError("paged KV cache needs window=0")
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    z = jnp.zeros((n_pages, kh, page_size, hd), cdtype_of(cfg))
    return {"kp": z, "vp": z}


def paged_prefill_scatter(cache, kv, page_rows):
    """Scatter a batched-prefill KV into the page pool.

    kv: {"k","v"} (B, Kh, Sp, hd) from ``attn_train(return_cache=True)``;
    Sp must be a multiple of the page size. page_rows (B, Sp // page) int32
    pool page ids; duplicate ids are only legal for trash pages (rows of a
    padded, non-admitted batch entry) since the scatter order is undefined.
    """
    kp = cache["kp"]
    _, kh, page, hd = kp.shape
    B, _, Sp, _ = kv["k"].shape
    assert Sp % page == 0, (Sp, page)
    npp = Sp // page
    flat = page_rows.reshape(B * npp)

    def scat(pool, x):  # x (B,Kh,Sp,hd) -> pages (B*npp,Kh,page,hd)
        xb = x.reshape(B, kh, npp, page, hd).transpose(0, 2, 1, 3, 4)
        return pool.at[flat].set(xb.reshape(B * npp, kh, page, hd))

    return dict(cache, kp=scat(kp, kv["k"]), vp=scat(cache["vp"], kv["v"]))


def attn_decode(p, cfg, x, cache, pos, *, page_table=None):
    """One-token decode. x (B,1,D).

    pos: scalar int32 (all slots aligned) or (B,) int32 per-slot positions
    (continuous batching; full cache only). Full cache: write at slot
    ``pos``. Ring cache (has "kpos"): write at ``pos % S`` and mask by
    stored positions. Paged cache (has "kp"): per-slot positions plus a
    (B, max_pages) ``page_table`` are required.
    """
    if "kp" in cache:
        if pos.ndim != 1 or page_table is None:
            raise ValueError("paged decode needs pos (B,) and a page_table")
        return _attn_decode_paged(p, cfg, x, cache, pos, page_table)
    is_ring = "kpos" in cache
    S = cache["k"].shape[2]
    if pos.ndim == 1:
        if is_ring:
            raise NotImplementedError("per-slot positions need a full cache")
        return _attn_decode_vec(p, cfg, x, cache, pos)
    positions = pos[None] if pos.ndim == 0 else pos
    q, k, v = _project_qkv(p, cfg, x, positions.astype(jnp.int32))
    slot = pos % S if is_ring else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=2)
    new_cache = dict(cache, k=ck, v=cv)
    if is_ring:
        new_cache["kpos"] = jax.lax.dynamic_update_slice(
            cache["kpos"], positions.astype(jnp.int32), (slot,))
        kpos = new_cache["kpos"]
        keep = (kpos >= 0) & (pos - kpos < (cfg.window or S)) & (kpos <= pos)
    else:
        kidx = jnp.arange(S, dtype=jnp.int32)
        keep = kidx <= pos
        if cfg.window:
            keep &= pos - kidx < cfg.window

    kf, vf = _repeat_kv(cfg, ck), _repeat_kv(cfg, cv)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhtk->bhqt", q.astype(jnp.float32) * scale,
                   kf.astype(jnp.float32))
    s = jnp.where(keep[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bhtk->bhqk", w, vf.astype(jnp.float32)).astype(x.dtype)
    return _out_proj(p, cfg, out), new_cache


def _attn_decode_vec(p, cfg, x, cache, pos):
    """Per-slot-position decode (pos (B,)): cache writes become a batched
    scatter (vmapped dynamic update); masking is per-example."""
    positions = pos[:, None].astype(jnp.int32)                 # (B,1)
    q, k, v = _project_qkv(p, cfg, x, positions)
    S = cache["k"].shape[2]

    upd = jax.vmap(lambda c, kk, s: jax.lax.dynamic_update_slice_in_dim(
        c, kk, s, axis=1))
    ck = upd(cache["k"], k, pos)
    cv = upd(cache["v"], v, pos)
    new_cache = dict(cache, k=ck, v=cv)

    kidx = jnp.arange(S, dtype=jnp.int32)
    keep = kidx[None, :] <= pos[:, None]                       # (B,S)
    if cfg.window:
        keep &= pos[:, None] - kidx[None, :] < cfg.window

    kf, vf = _repeat_kv(cfg, ck), _repeat_kv(cfg, cv)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhtk->bhqt", q.astype(jnp.float32) * scale,
                   kf.astype(jnp.float32))
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bhtk->bhqk", w, vf.astype(jnp.float32)).astype(x.dtype)
    return _out_proj(p, cfg, out), new_cache


def _attn_decode_paged(p, cfg, x, cache, pos, page_table):
    """Paged per-slot decode: cache {"kp","vp"} (P,Kh,page,hd) pool;
    page_table (B, max_pages) int32 pool page ids; pos (B,) positions.

    Write: slot b's token lands at pool[table[b, pos//page], :, pos%page]
    (a batched scatter — active slots own disjoint pages). Read: gather the
    slot's pages in logical order into (B, Kh, max_pages*page, hd) and mask
    exactly like ``_attn_decode_vec`` — same keys, same order, so the two
    layouts agree numerically.
    """
    kp, vp = cache["kp"], cache["vp"]
    _, kh, page, hd = kp.shape
    maxp = page_table.shape[1]
    positions = pos[:, None].astype(jnp.int32)                 # (B,1)
    q, k, v = _project_qkv(p, cfg, x, positions)

    pids = jnp.take_along_axis(page_table, (pos // page)[:, None], axis=1)
    pids = pids[:, 0]                                          # (B,)
    offs = pos % page
    ck = kp.at[pids, :, offs].set(k[:, :, 0, :])
    cv = vp.at[pids, :, offs].set(v[:, :, 0, :])
    new_cache = dict(cache, kp=ck, vp=cv)

    B = pos.shape[0]
    S = maxp * page
    ks = ck[page_table].transpose(0, 2, 1, 3, 4).reshape(B, kh, S, hd)
    vs = cv[page_table].transpose(0, 2, 1, 3, 4).reshape(B, kh, S, hd)

    kidx = jnp.arange(S, dtype=jnp.int32)
    keep = kidx[None, :] <= pos[:, None]                       # (B,S)

    kf, vf = _repeat_kv(cfg, ks), _repeat_kv(cfg, vs)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhtk->bhqt", q.astype(jnp.float32) * scale,
                   kf.astype(jnp.float32))
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bhtk->bhqk", w, vf.astype(jnp.float32)).astype(x.dtype)
    return _out_proj(p, cfg, out), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg):
    return init_attention(key, cfg)


def cross_kv(p, cfg, enc_out):
    """Precompute cross K/V from encoder output (B,F,D) -> (B,Kh,F,hd)."""
    cd = cdtype_of(cfg)
    k = jnp.einsum("bfd,dhk->bhfk", enc_out, p["wk"].astype(cd))
    v = jnp.einsum("bfd,dhk->bhfk", enc_out, p["wv"].astype(cd))
    return {"ck": constrain(k, "cache_batch", "cache_kv_heads", None, None),
            "cv": constrain(v, "cache_batch", "cache_kv_heads", None, None)}


def attn_cross(p, cfg, x, ckv):
    """x (B,Sq,D) attends over precomputed cross K/V (no mask, no rope on q
    per our whisper variant — see DESIGN.md)."""
    cd = cdtype_of(cfg)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(cd))
    kf, vf = _repeat_kv(cfg, ckv["ck"]), _repeat_kv(cfg, ckv["cv"])
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhtk->bhqt", q.astype(jnp.float32) * scale,
                   kf.astype(jnp.float32))
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bhtk->bhqk", w, vf.astype(jnp.float32)).astype(x.dtype)
    return _out_proj(p, cfg, out)
