"""Plain reference of a Llama-architecture decoder's forward, in float32.

The published description (DeepSeek-Coder, arXiv:2401.14196 section 3.4,
and its hf ``config.json``: the Llama block): token embedding; per layer a
pre-norm RMSNorm, grouped-query causal self-attention with rotary position
embeddings (rotate-half form, inverse frequencies ``theta ** (-2i / d)``,
positions divided by the linear scaling factor), a residual add, a second
RMSNorm, a SwiGLU MLP ``down(silu(gate x) * up x)`` and a residual add; a
final RMSNorm and an untied output head. No biases.

It takes the weights as data, by role, and imports nothing of the program:

* ``embed`` (vocab, d); ``final_norm`` (d,); ``head`` (d, vocab);
* ``layers``: a sequence of mappings, one per layer, each with
  ``attn_norm`` (d,), ``wq`` (d, heads, head_dim), ``wk`` and ``wv``
  (d, kv_heads, head_dim), ``wo`` (heads, head_dim, d), ``mlp_norm`` (d,),
  ``w_gate`` and ``w_up`` (d, ff), ``w_down`` (ff, d). It is read one layer
  at a time, so a sequence that slices each layer out on access keeps one
  layer in float32 beside weights held in a narrower type.

Departures from the description, none of which changes a logit it returns:

* every product and sum is float32 under
  ``jax.default_matmul_precision("highest")``, where the model states
  bfloat16;
* there is no cache and no batching: each sequence runs alone from its
  first token, right-padded to a common length so that one compiled layer
  serves them all (attention is causal, so the padding never reaches a
  real position);
* attention runs blocked over the key/value heads, one group of query
  heads at a time, to bound the scores' memory;
* the head is applied at each sequence's last position only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_ROLES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
               "w_up", "w_down")
PAD_TO = 128                  # sequences pad to a multiple of this


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta, factor):
    """x (S, heads, hd) at positions 0..S-1, rotate-half form."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(s, dtype=jnp.float32) / factor)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "factor"))
def _layer(h, w, *, eps, theta, factor):
    """One block over one sequence h (S, d), float32."""
    s = h.shape[0]
    heads, kv_heads, hd = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    group = heads // kv_heads
    x = _rms(h, w["attn_norm"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", x, w["wq"]), theta, factor)
    k = _rope(jnp.einsum("sd,dhk->shk", x, w["wk"]), theta, factor)
    v = jnp.einsum("sd,dhk->shk", x, w["wv"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_group(qkv):          # the query heads of one key/value head
        qg, kg, vg = qkv         # (S, group, hd), (S, hd), (S, hd)
        sc = jnp.einsum("qgk,tk->gqt", qg, kg) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqt,tk->qgk", p, vg)

    out = jax.lax.map(one_group, (
        q.reshape(s, kv_heads, group, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(s, heads, hd)
    h = h + jnp.einsum("shk,hkd->sd", out, w["wo"])
    x = _rms(h, w["mlp_norm"], eps)
    mlp = jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])
    return h + mlp @ w["w_down"]


def last_logits(weights, seqs, *, norm_eps: float, rope_theta: float,
                rope_factor: float = 1.0) -> np.ndarray:
    """The logits that follow each token sequence in ``seqs`` (lists of
    token ids), float64, shape (len(seqs), vocab)."""
    def f32(x):                  # upcast on the device, not the host
        return jnp.asarray(x).astype(jnp.float32)

    n = max(len(t) for t in seqs)
    n = -(-n // PAD_TO) * PAD_TO
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(weights["embed"])
        hs = []
        for toks in seqs:
            ids = np.zeros(n, np.int32)
            ids[:len(toks)] = toks
            hs.append(f32(jnp.take(embed, jnp.asarray(ids), axis=0)))
        for lw in weights["layers"]:
            w = {r: f32(lw[r]) for r in LAYER_ROLES}
            hs = [_layer(h, w, eps=norm_eps, theta=rope_theta,
                         factor=rope_factor) for h in hs]
            del w
        last = jnp.stack([h[len(t) - 1] for h, t in zip(hs, seqs)])
        last = _rms(last, f32(weights["final_norm"]), norm_eps)
        logits = last @ f32(weights["head"])
    return np.asarray(logits, np.float64)


def max_rel_err(got, want) -> float:
    """max |got - want| / max |want| over every entry."""
    got = np.asarray(got, np.float64).reshape(np.shape(want))
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))
