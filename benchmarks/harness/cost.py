"""Bytes and operations one decode step needs, from the shapes alone (the
benchmark's copy of the arithmetic in ``dllama_tpu/obs/cost.py``; that one
reads live engine state, this one reads the configuration file).

A decode step reads every weight once whatever the batch (Q40: 18 bytes per 32
values; the f32 embedding contributes only the rows looked up) and, for every
row in the batch, the keys and values of that row's live context.
"""

from __future__ import annotations


def weight_bytes(cfg: dict, chips: int = 1) -> float:
    """Packed Q40 bytes of the matrices a step streams, per chip."""
    dim, hid, voc = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["head_dim"] * cfg["num_key_value_heads"]
    per_layer = 2 * dim * dim + 2 * dim * kv + 3 * dim * hid
    values = cfg["num_hidden_layers"] * per_layer + voc * dim
    return values * 18 / 32 / chips


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * elem_bytes / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights once, plus the
    live context of every row (``live_context_tokens`` summed over rows)."""
    return weight_bytes(cfg, chips) + kv_bytes_per_token(cfg, chips) * live_context_tokens


def step_flops(cfg: dict, rows: int, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip."""
    dim, hid, voc = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["head_dim"] * cfg["num_key_value_heads"]
    mat = cfg["num_hidden_layers"] * (2 * dim * dim + 2 * dim * kv + 3 * dim * hid) + voc * dim
    att = 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * (mat * rows + att * live_context_tokens) / chips
