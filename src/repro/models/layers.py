"""Shared building blocks: norms, rope, embeddings, gated MLPs.

Every ``init_*`` has a paired ``spec_*`` returning the SAME tree structure with
logical-axis tuples as leaves (resolved by repro.parallel.sharding.resolve).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain


def dtype_of(cfg):
    return jnp.dtype(cfg.param_dtype)


def cdtype_of(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------------
# RMSNorm
# ----------------------------------------------------------------------------

def init_rmsnorm(key, dim, cfg):
    del key
    return {"scale": jnp.ones((dim,), dtype_of(cfg))}


def spec_rmsnorm():
    return {"scale": (None,)}


def rmsnorm(p, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------------

def rope_angles(positions, head_dim, theta, scaling=1.0):
    """positions: int array (...,) -> (cos, sin) of shape (..., head_dim//2), f32.

    ``scaling`` > 1 is linear RoPE scaling (position interpolation): every
    position is divided by the factor. At 1 the angles are computed exactly
    as without it."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos = positions.astype(jnp.float32)
    if scaling != 1.0:
        pos = pos / scaling
    ang = pos[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., S, H, D); cos/sin: (S, D//2), (B, S, D//2) (per-example
    positions, continuous batching) or broadcastable (..., S, 1, D//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim in (x1.ndim - 2, x1.ndim - 1):  # insert the head axis
        cos, sin = cos[..., None, :], sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------------

def init_embedding(key, cfg):
    p = {"table": _normal(key, (cfg.vocab_size, cfg.d_model), 0.02, dtype_of(cfg))}
    if not cfg.tie_embeddings:
        p["head"] = _normal(jax.random.fold_in(key, 1),
                            (cfg.d_model, cfg.vocab_size),
                            cfg.d_model ** -0.5, dtype_of(cfg))
    return p


def spec_embedding(cfg):
    s = {"table": ("vocab", "fsdp")}
    if not cfg.tie_embeddings:
        s["head"] = ("fsdp", "vocab")
    return s


def embed(p, tokens, cfg):
    h = jnp.take(p["table"], tokens, axis=0).astype(cdtype_of(cfg))
    return constrain(h, "batch", "seq", "d_model")


def unembed(p, h, cfg):
    table = p["head"] if "head" in p else p["table"].T
    logits = jnp.einsum("bsd,dv->bsv", h, table.astype(cdtype_of(cfg)))
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")


# ----------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ----------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _normal(k1, (d, f), d ** -0.5, dtype_of(cfg)),
        "w_up": _normal(k2, (d, f), d ** -0.5, dtype_of(cfg)),
        "w_down": _normal(k3, (f, d), f ** -0.5, dtype_of(cfg)),
    }


def spec_mlp():
    return {"w_gate": ("fsdp", "ff"), "w_up": ("fsdp", "ff"), "w_down": ("ff", "fsdp")}


def _act(name, x):
    if name == "geglu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)  # swiglu


def mlp(p, x, cfg):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    hidden = _act(cfg.act, g) * u
    hidden = constrain(hidden, "batch", "seq", "ff")
    out = jnp.einsum("bsf,fd->bsd", hidden, p["w_down"])
    return constrain(out, "batch", "seq", "d_model")


# ----------------------------------------------------------------------------
# Cross-entropy (fp32, vocab-sharded safe)
# ----------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """logits (B,S,V), labels (B,S) int32, mask (B,S) 1=count. Returns mean nll."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
