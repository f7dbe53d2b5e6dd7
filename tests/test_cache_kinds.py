"""What each kind of cache can do, asked of the program from outside: every
tiny configuration against every capability an operator can ask for (a tp / sp /
ep mesh, ``--kv-quant int8`` in each spelling, ``--kv-pages``, a hand-off,
writing a request's pages, ``--kv-reserve optimistic``, a step over a slot's
rows, the radix tree and preemption).  Each case runs, or is refused in the
sentence ``MATRIX`` holds.  The matrix was recorded from the program before
``models/cache_kinds.py`` existed (PR 63, Step 0) and is what that table was
filled in from.
"""

import ast
import functools
import pathlib

import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.io import mfile
from dllama_tpu.models import config as config_mod
from dllama_tpu.models.params import init_params
from dllama_tpu.models.transformer import (forward_slots, init_kv_cache,
                                           init_kv_pool)
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler

CONFIGS = {
    "llama": config_mod.tiny_config,
    "mixtral": functools.partial(config_mod.tiny_config, arch=mfile.ARCH_MIXTRAL,
                                 n_experts=4, n_active_experts=2),
    "olmoe": functools.partial(config_mod.tiny_config, arch=mfile.ARCH_OLMOE,
                               n_experts=4, n_active_experts=2),
    "deepseek2": config_mod.tiny_deepseek2,
    "smallthinker": config_mod.tiny_smallthinker,
    "exaone_moe": config_mod.tiny_exaone_moe,
    "lfm2_moe": config_mod.tiny_lfm2_moe,
    "brumby": config_mod.tiny_brumby,
    "ouro": config_mod.tiny_ouro,
    "falcon_h1": config_mod.tiny_falcon_h1,
    "granitemoehybrid": config_mod.tiny_granite_hybrid,
}


@functools.cache
def _model(name):
    cfg = CONFIGS[name]()
    return cfg, init_params(cfg, seed=5, scale=0.08)


def _one_device():
    return make_mesh(tp=1, devices=jax.devices()[:1])


@functools.cache
def _engine(name, paged=True):
    """The configuration's slot engine, one a module: two slots over a pool
    of pages wherever the model takes pages (and the contiguous one beside it
    for the one probe that asks it)."""
    cfg, params = _model(name)
    try:
        return Engine(cfg, params, mesh=_one_device(), batch=2, seq_len=64,
                      **(dict(kv_pages=49, kv_page_size=4) if paged else {}))
    except ValueError:
        return _engine(name, False)


@pytest.fixture(autouse=True, scope="module")
def _engines_go_with_the_module():
    yield
    _engine.cache_clear()
    _model.cache_clear()


def _new_engine(name, mesh=None, **kw):
    cfg, params = _model(name)
    if mesh is None:
        mesh = _one_device()
    else:
        mesh = make_mesh(**{mesh: 2}, devices=jax.devices()[:2])
    Engine(cfg, params, mesh=mesh, batch=1, **kw)


def _scheduler(name, paged=True, **kw):
    SlotScheduler(_engine(name, paged), **kw).close()


def _write_pages(name):
    eng = _engine(name)
    eng.write_pool_pages([1], eng.read_pool_pages([1]))


def _traced_step(name, rows=64):
    """A slot step of 64 rows, traced and not run."""
    cfg, params = _model(name)
    eng = _engine(name)
    table = jnp.zeros((2, eng.max_pages_per_slot), jnp.int32) if eng.paged else None
    jax.eval_shape(
        lambda c: forward_slots(params, cfg, jnp.zeros((2, rows), jnp.int32), c,
                                jnp.zeros((2,), jnp.int32),
                                jnp.full((2,), rows, jnp.int32), table),
        eng.cache)


def _reuse_and_preemption(name):
    sched = SlotScheduler(_engine(name), prefix_reuse=True, preempt=True)
    try:
        on = (sched.prefix_cache is not None, bool(sched.preempt))
        if not any(on):  # and nothing leaves such a scheduler page by page
            assert sched.handoff_export_all() == {}
            assert sched.checkpoint_export("x") is None
        return on
    finally:
        sched.close()


PROBES = {
    "tp": functools.partial(_new_engine, mesh="tp"),
    "sp": functools.partial(_new_engine, mesh="sp"),
    "ep": functools.partial(_new_engine, mesh="ep"),
    "int8-q8": functools.partial(_new_engine, kv_dtype="q8"),
    "int8-dtype": functools.partial(_new_engine, kv_dtype=jnp.int8),
    "int8-paged": functools.partial(_new_engine, kv_dtype="q8", kv_pages=20,
                                    kv_page_size=4),
    "int8-cache": lambda name: init_kv_cache(_model(name)[0], 1, quant=True) and None,
    "int8-pool": lambda name: init_kv_pool(_model(name)[0], 4, 4, quant=True,
                                           slots=1) and None,
    "kv-pages": functools.partial(_new_engine, kv_pages=40, kv_page_size=4),
    "pool-of-no-slots": lambda name: init_kv_pool(_model(name)[0], 8, 4) and None,
    "hand-off": lambda name: _engine(name).handoff_fingerprint() and None,
    "write-pages": _write_pages,
    "optimistic": functools.partial(_scheduler, kv_reserve="optimistic"),
    "step-32": functools.partial(_scheduler, prefill_chunk=32),
    "step-32-contiguous": functools.partial(_scheduler, paged=False,
                                            prefill_chunk=32),
    "traced-step-64": _traced_step,
    "reuse+preempt": _reuse_and_preemption,
}


def outcome(name, cap):
    """What the program answers: ``None`` (it runs), a probe's own reading, or
    the refusal as ``Type: sentence``."""
    try:
        return PROBES[cap](name)
    except (ValueError, TypeError, KeyError) as e:
        return f"{type(e).__name__}: {e}"


def _on_one_device(what, why, axes=("tp", "sp", "ep")):
    """What ``Engine`` says of a kind that runs on one device and has no int8
    form; a dense model's ``ep`` is refused before its cache is asked."""
    said = {ax: f"ValueError: {what} runs on one device: a {ax}=2 mesh is not "
                f"supported for this architecture ({why}; not wired)" for ax in axes}
    said.update(dict.fromkeys(
        ("int8-q8", "int8-dtype", "int8-paged"),
        f"ValueError: --kv-quant int8 is not supported with {what}: its cache "
        "has no int8 form"))
    return said


def _no_int8_form(opening):
    return dict.fromkeys(("int8-cache", "int8-pool"), (
        f"ValueError: {opening} (--kv-quant int8 is refused for this architecture)"))


def _a_slot_owns(arch, owns, contiguous=False):
    """What moves a request's cache page by page, and a step over 16 rows, for a
    model whose slots own ``owns``; ``contiguous``: its contiguous slot engine
    holds a step to 16 rows too."""
    step = ("ValueError: a step of more than 16 rows (--sched-prefill-chunk 32, "
            f"--spec-k 4) does not fit a slot's {owns}")
    said = {cap: f"ValueError: {what} is not supported for a {arch} model: a "
                 f"slot's {owns} cannot be carried page by page"
            for cap, what in (
                ("hand-off", "per-request hand-off (DLREQ01)"),
                ("write-pages", "writing a request's pages into the pool"),
                ("optimistic", "--kv-reserve optimistic (the spill tier)"))}
    said.update({"step-32": step, "reuse+preempt": (False, False)})
    if contiguous:
        said["step-32-contiguous"] = step
    return said


_DENSE = {"ep": "ValueError: ep>1 needs an MoE model (no expert axis to shard)"}
_RUNS = {**dict.fromkeys(PROBES), "reuse+preempt": (True, True)}
_WINDOW_RING = ("ValueError: a step of 64 rows does not fit a window layer's ring "
                "of 9 pages of 4 (window 16): the engine sized it for fewer rows "
                "a step")
_RECENT_RING = ("ValueError: a call of 64 rows does not fit a retention layer's "
                "ring of 128 recent positions: feed at most 32 rows a call")
_TWO_KINDS = "its two cache kinds have one placement"
_RING_A_SLOT = {"pool-of-no-slots": (
    "ValueError: a windowed model's pool needs the number of slots: each owns a "
    "ring of pages in the window layers' planes")}
# as the program answered at PR 62's commit, before the table: only what is not
# "runs" is written out
MATRIX = {name: {**_RUNS, **said} for name, said in {
    "llama": _DENSE,
    "mixtral": {},
    "olmoe": {},
    "deepseek2": {
        **_on_one_device("latent attention (MLA)", "the latent cache would be "
                         "replicated and the heads sharded"),
        **_no_int8_form("a latent (MLA) cache has no int8 form yet: the latent "
                        "and the rotated key want a scale each")},
    "smallthinker": {
        **_on_one_device("a windowed (smallthinker) model", _TWO_KINDS),
        **_no_int8_form("a cache with window layers has no int8 form yet"),
        **_RING_A_SLOT,
        **_a_slot_owns("smallthinker", "window layers' rings"),
        "traced-step-64": _WINDOW_RING},
    "exaone_moe": {
        **_on_one_device("a windowed (exaone_moe) model", _TWO_KINDS),
        **_no_int8_form("a cache with window layers has no int8 form yet"),
        **_RING_A_SLOT,
        **_a_slot_owns("exaone_moe", "window layers' rings"),
        "traced-step-64": _WINDOW_RING},
    "lfm2_moe": {
        **_on_one_device("a convolution (lfm2_moe) model", _TWO_KINDS),
        **_no_int8_form("a cache with window layers has no int8 form yet"),
        **_RING_A_SLOT,
        **_a_slot_owns("lfm2_moe", "convolution layers' state"),
        "traced-step-64": "ValueError: a slot step of 64 rows does not fit a "
                          "convolution layer's state ring of 64 positions: feed "
                          "at most 62 rows a step"},
    "brumby": {
        **_on_one_device("a retention (brumby) model", "its state a kv head is "
                         "replicated with its slot", axes=("tp", "sp")),
        **_DENSE,
        **_no_int8_form("a retention state has no int8 form"),
        **_a_slot_owns("brumby", "retention layers' state", contiguous=True),
        "kv-pages": "ValueError: a retention (brumby) model keeps no keys and "
                    "values, so it has no pages to count: drop --kv-pages (its "
                    "slots are admitted by --batch-slots alone)",
        "traced-step-64": _RECENT_RING},
    # a looped model's int8 planes can be made (int8-cache, int8-pool run): it is
    # the engine that has not tried them
    "ouro": {
        **_on_one_device("a looped (ouro) model", "its cache is a plane a (pass, "
                         "layer), which no placement or scale plane has been "
                         "tried on", axes=("tp", "sp")),
        **_DENSE},
    "falcon_h1": {
        **_on_one_device("a state-space (falcon_h1) model", "its state a head is "
                         "replicated with its slot", axes=("tp", "sp")),
        **_DENSE,
        **_no_int8_form("a state-space mixer's state has no int8 form"),
        **_a_slot_owns("falcon_h1", "state-space mixers' state", contiguous=True),
        "pool-of-no-slots": "ValueError: a pool beside a state-space mixer needs "
                            "the number of slots: each owns a state and its rings",
        "traced-step-64": _RECENT_RING},
    # a layer of a slot owns a state OR pages (Falcon-H1: both): the same row of
    # the table, so the same sentences; its experts do not lift the ep refusal
    "granitemoehybrid": {
        **_on_one_device("a state-space (granitemoehybrid) model", "its state a "
                         "head is replicated with its slot"),
        **_no_int8_form("a state-space mixer's state has no int8 form"),
        **_a_slot_owns("granitemoehybrid", "state-space mixers' state",
                       contiguous=True),
        "pool-of-no-slots": "ValueError: a pool beside a state-space mixer needs "
                            "the number of slots: each owns a state and its rings",
        "traced-step-64": _RECENT_RING},
}.items()}


@pytest.mark.parametrize("cap", list(PROBES))
@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_capability_runs_or_is_refused_in_its_sentence(name, cap):
    assert outcome(name, cap) == MATRIX[name][cap]


# ---- the seam ------------------------------------------------------------------

_POLICY = {"is_mla", "periodic", "attention_free", "has_ssm", "keeps_state",
           "folds_state"}
_ROOT = pathlib.Path(__file__).resolve().parent.parent / "dllama_tpu"


def _policy_reads(tree):
    """Reads of a ``ModelConfig`` predicate that decides what a cache may do,
    and comparisons of an arch id (``mfile.ARCH_NAMES[cfg.arch]`` in a message
    is neither)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _POLICY:
            yield node.lineno, node.attr
        if isinstance(node, ast.Compare) and any(
                isinstance(n, ast.Attribute) and n.attr == "arch"
                for n in ast.walk(node)):
            yield node.lineno, "arch compared"


@pytest.mark.parametrize("layer", ["runtime", "parallel", "server"])
def test_no_module_above_the_models_decides_what_a_cache_may_do(layer):
    """``models/cache_kinds.py`` is asked; sizes (``cfg.ssm_conv``,
    ``cfg.n_loops``) may be read anywhere."""
    found = [f"{path.relative_to(_ROOT)}:{line} {what}"
             for path in sorted((_ROOT / layer).glob("*.py"))
             for line, what in _policy_reads(ast.parse(path.read_text()))]
    assert not found, found
