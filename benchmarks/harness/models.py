"""One module per architecture, found by name the way a per-layer reader is:
a configuration file names its module with the key ``"model"`` (a file
without the key means ``dense``), and ``benchmarks/models/<name>.py`` holds
everything the yardstick knows about that architecture: the ``.m`` file's
layout, the plain reference and the cost arithmetic.  The rest of the
benchmark reaches a module through ``for_config`` and never imports one by
name, so a new architecture is a new file and no edit.

A module exports exactly ``EXPORTS``:

* ``shape(config) -> dict``: the ``.m`` header's sizes from the
  configuration's published keys, with its consistency checks; ``REHEARSE``:
  the toy overrides ``--rehearse`` applies to it;
* ``header(shape) -> bytes`` and ``plan(shape) -> [(name, shape, ftype,
  offset, nbytes)]``: the file layout (``mformat.pack_header`` and
  ``mformat.lay_out`` do the arithmetic every layout shares);
* ``last_logits(model_path, prompts) -> float32 (n, vocab)``: the plain
  reference, with its departures from the published model in its docstring;
* ``weight_bytes(cfg, chips=1, rows=1)``, ``kv_bytes_per_token(cfg, chips=1,
  elem_bytes=2)``, ``step_bytes(cfg, live_context_tokens, chips=1, rows=1)``,
  ``step_flops(cfg, rows, live_context_tokens, chips=1)``: what a decode step
  needs (``harness/cost.py`` hands its four names to these).
"""

from __future__ import annotations

import functools
import importlib.util
import os

MODELS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "models")
DEFAULT = "dense"
EXPORTS = ("shape", "REHEARSE", "header", "plan", "last_logits", "weight_bytes",
           "kv_bytes_per_token", "step_bytes", "step_flops")


@functools.lru_cache(maxsize=None)
def load(name: str):
    path = os.path.join(MODELS, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no model module {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location("benchmarks_models_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [k for k in EXPORTS if not hasattr(mod, k)]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)}")
    return mod


def for_config(config: dict):
    """The module of a configuration (the parsed file under ``configs/``)."""
    return load(config.get("model", DEFAULT))
