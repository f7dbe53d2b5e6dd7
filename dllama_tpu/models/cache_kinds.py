"""What the program knows about a kind of cache: one row a kind.

A model leaves behind one or two kinds (``ModelConfig.cache_kinds``): ``full``
keys and values a position (a plane a layer; a plane a (pass, layer) in a looped
model, the row ``LOOPED``), MLA's ``latent``, a ``window`` layer's ring, a short
convolution's (``conv``), a ``retention`` layer's state and ring of recent
positions, a state-space mixer's (``ssm``).  A model's answer is the meet over
its rows.  The engines, the scheduler and the sharding ask here and read no
predicate of ``ModelConfig`` to decide what a cache may do: docs/SERVING.md has
the table, ``tests/test_cache_kinds.py`` every answer and the seam.  What lifts
a refusal empties the row's field and turns its case there to "runs".
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from ..obs import metrics as obs_metrics
from ..ops import conv, retention

# rows of the widest step a slot's own planes are sized for (a window layer's
# ring of pages): the scheduler's default ``--sched-prefill-chunk``, and at
# least a verify step's ``spec_k + 1``; a wider step is refused by name
SLOT_ROWS = 16


@dataclass(frozen=True)
class Kind:
    name: str
    planes: tuple[str, ...]   # the ``KVCache`` fields it owns
    gauge: str                # its label of ``kv_cache_bytes{kind}``
    # what a cached token adds: its share of the plane over all "tokens", over a
    # "ring"'s positions (nothing on a paged engine: a slot's ring of pages is
    # there whatever the depth), or nothing ("")
    grows_by: str = ""
    # ---- what it refuses and in which words; empty: it runs
    what: str = ""            # the noun a refusal prints ({arch}: the file's)
    one_device: str = ""      # why no tp / sp / ep mesh and no int8 in an engine
    no_int8_form: str = ""    # what ``init_kv_cache`` / ``init_kv_pool`` say of quant
    no_pages: str = ""        # what ``--kv-pages`` is told
    # what of it a slot owns that no page id addresses (``slot_unpaged``: on a
    # contiguous slot engine too): nothing then moves the request's cache page
    # by page (hand-off, ``write_pool_pages``, ``--kv-reserve optimistic``, the
    # radix tree, preemption) and a step is ``SLOT_ROWS`` rows at most
    slot_owns: str = ""
    slot_unpaged: bool = False
    # ---- its recurrent state's account (``runtime/engine.py``): the counter of
    # rewinds and its label for one too deep, the counter of folds where the
    # state lags the clock behind a watermark (``ops/retention.py``), the
    # config's field with the taps of its convolution's ring
    rewinds: obs_metrics.LabeledCounter | None = None
    too_deep: str = "refused"
    folds: obs_metrics.Counter | None = None
    taps_field: str = ""
    # the config's field with how many layers keep this kind (a fold is a block
    # in each of them)
    depth_field: str = "n_layers"

    def depth(self, cfg) -> int:
        return getattr(cfg, self.depth_field)

    def taps(self, cfg) -> int:
        return getattr(cfg, self.taps_field) if self.taps_field else 0

    def max_burst(self, cfg, chunk: int) -> int:
        """A decode burst is capped so that the deepest rewind (two pipelined
        bursts less one position) stays addressed by position."""
        if self.folds:
            chunk = min(chunk, retention.max_burst())
        taps = self.taps(cfg)
        return min(chunk, conv.max_burst(conv.RING, taps)) if taps else chunk


FULL = Kind("full", ("k", "v", "k_scale", "v_scale"), "full", grows_by="tokens")
LOOPED = replace(
    FULL, what="a looped ({arch}) model",
    one_device="its cache is a plane a (pass, layer), which no placement or "
               "scale plane has been tried on")
LATENT = Kind(
    "latent", ("k", "v"), "full", grows_by="tokens", what="latent attention (MLA)",
    one_device="the latent cache would be replicated and the heads sharded",
    no_int8_form="a latent (MLA) cache has no int8 form yet: the latent and the "
                 "rotated key want a scale each")
WINDOW = Kind(
    "window", ("wk", "wv"), "window", grows_by="ring",
    what="a windowed ({arch}) model",
    one_device="its two cache kinds have one placement",
    no_int8_form="a cache with window layers has no int8 form yet",
    slot_owns="window layers' rings")
# a period's other layers: convolutions stand where the window layers would
# (``models/windowed.py``) and refuse in the same words
CONV = replace(
    WINDOW, name="conv", planes=("cz",), gauge="conv", grows_by="",
    what="a convolution ({arch}) model", slot_owns="convolution layers' state",
    rewinds=obs_metrics.CONV_STATE_REWINDS, too_deep="reprefill",
    taps_field="conv_taps")
RETENTION = Kind(
    "retention", ("rs", "rz", "rk", "rv", "rg", "rw"), "retention",
    what="a retention ({arch}) model",
    one_device="its state a kv head is replicated with its slot",
    no_int8_form="a retention state has no int8 form",
    no_pages="keeps no keys and values, so it has no pages to count: drop "
             "--kv-pages (its slots are admitted by --batch-slots alone)",
    slot_owns="retention layers' state", slot_unpaged=True,
    rewinds=obs_metrics.RETENTION_REWINDS, folds=obs_metrics.RETENTION_FOLDS)
# the mixer stands in retention's fields at its own sizes (rk holds B, rv x, rg
# dt; no rz) and in ``cz`` for its convolution's ring.  One row for both of its
# models: a layer of a slot owns a state AND pages (Falcon-H1, every block) or a
# state OR pages (Granite, by the layer's place in its period); either way the
# planes are ``cfg.n_ssm_layers`` deep and ``full``'s ``cfg.n_full_layers``
SSM = Kind(
    "ssm", ("rs", "rk", "rv", "rg", "rw", "cz"), "ssm",
    what="a state-space ({arch}) model",
    one_device="its state a head is replicated with its slot",
    no_int8_form="a state-space mixer's state has no int8 form",
    slot_owns="state-space mixers' state", slot_unpaged=True,
    rewinds=obs_metrics.SSM_STATE_REWINDS, folds=obs_metrics.SSM_FOLDS,
    taps_field="ssm_conv", depth_field="n_ssm_layers")
KINDS = (FULL, LOOPED, LATENT, WINDOW, CONV, RETENTION, SSM)


def owner(cfg, name: str) -> Kind:
    """The row that owns the plane ``name`` of this model's cache; ``k`` and ``v``
    of a model no layer of which keeps keys and values are empty, and ``full``'s."""
    return next((k for k in cfg.cache_kinds if name in k.planes), FULL)


def refuse_int8(cfg) -> None:
    for kind in cfg.cache_kinds:
        if kind.no_int8_form:
            raise ValueError(f"{kind.no_int8_form} (--kv-quant int8 is refused "
                             "for this architecture)")
