"""Bytes and operations one decode step needs, from the shapes alone (the
benchmark's copy of the arithmetic in ``dllama_tpu/obs/cost.py``; that one
reads live engine state, this one reads the configuration file).

The arithmetic belongs to the architecture: each name here hands its call to
the configuration's module (``models/<name>.py``, found by
``models.for_config``), so a reader that calls these serves every
configuration.  ``rows`` is the number of rows decoding together: a step's
weight bytes depend on it only where routing decides which experts are read.
"""

from __future__ import annotations

from . import models


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed bytes of the matrices a step of ``rows`` rows streams, per chip."""
    return models.for_config(cfg).weight_bytes(cfg, chips, rows)


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip."""
    return models.for_config(cfg).kv_bytes_per_token(cfg, chips, elem_bytes)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights once, plus the
    live context of every row (``live_context_tokens`` summed over rows)."""
    return models.for_config(cfg).step_bytes(cfg, live_context_tokens, chips, rows)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip."""
    return models.for_config(cfg).step_flops(cfg, rows, live_context_tokens, chips)
