"""Bytes and operations of one decode tick of a Llama-architecture decoder,
from shapes.

The yardstick's numerators for the serve cells: a roofline share divides
the bytes by a device time from the trace and the HBM bandwidth, an MFU
share divides the operations by the same time and the bfloat16 peak. The
shapes come from a configuration file (``chipbench/configs/``); nothing
here reads the program.

One tick serves ``slots`` sequences one token each. It must read every
weight a token passes through once (each layer's attention and MLP
matrices and both norms, the final norm, the output head; the embedding
table only in the rows of the tick's tokens) and every live position's
key and value in every layer. Its operations are two a weight for each
token's matrix-vector products, and, in each layer, two a head dimension
for the scores and two for the weighted sum of values, per live position
and query head.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def param_bytes(c: dict) -> int:
    """Bytes of one weight in the configuration's ``dtype``."""
    return DTYPE_BYTES[c["dtype"]]


def layer_params(c: dict) -> int:
    """Parameters of one layer: q, k, v, o projections, SwiGLU, two norms."""
    d, ff = c["d_model"], c["d_ff"]
    q = c["n_heads"] * c["head_dim"]
    kv = c["n_kv_heads"] * c["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff + 2 * d


def tick_weight_params(c: dict) -> int:
    """Parameters every tick reads whole: the layers, the final norm and
    the untied head (the embedding table is read a row a token)."""
    return c["n_layers"] * layer_params(c) + c["d_model"] \
        + c["d_model"] * c["vocab_size"]


def kv_bytes_per_position(c: dict) -> int:
    """Key and value of one position over every layer."""
    return 2 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] \
        * param_bytes(c)


def tick_bytes(c: dict, slots: int, live_positions: int) -> int:
    """Compulsory HBM bytes of one tick: the weights once, the ``slots``
    embedding rows, and the keys and values of ``live_positions`` positions
    (summed over the slots, the tick's own included)."""
    b = param_bytes(c)
    return tick_weight_params(c) * b + slots * c["d_model"] * b \
        + live_positions * kv_bytes_per_position(c)


def tick_matmul_flops(c: dict, slots: int) -> int:
    """Matrix-vector operations of one tick: two a matrix weight a token."""
    norms = (2 * c["n_layers"] + 1) * c["d_model"]
    return 2 * slots * (tick_weight_params(c) - norms)


def attention_flops_per_position(c: dict) -> int:
    """Scores and weighted values of one live position, every layer."""
    return 4 * c["n_layers"] * c["n_heads"] * c["head_dim"]


def tick_flops(c: dict, slots: int, live_positions: int) -> int:
    return tick_matmul_flops(c, slots) \
        + live_positions * attention_flops_per_position(c)
