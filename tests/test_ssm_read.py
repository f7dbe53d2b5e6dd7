"""A decoded token's recent rows (``ops/ssm.py``, PR 67): at one token a row the
part ``recent`` of ``ssm.read`` is ONE launch a mixer layer over the positions
that are live (``recent_walk``) where five XLA ops read the whole ring
(``_recent``).  The launch (in interpret mode, at toy widths) is held to the XLA
form at 1e-6 of the output's largest value; the rule (``_read_form``) to its
static facts; the programs the rule leaves alone (more than one token a row,
one row, a mesh, the CPU) to the jaxprs they had before it."""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import config as config_mod
from dllama_tpu.models.params import init_params
from dllama_tpu.models.transformer import (forward, forward_slots, init_kv_cache,
                                           init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch
from dllama_tpu.ops import ssm
from dllama_tpu.ops.retention import FOLD, REWIND, RING
from dllama_tpu.parallel.mesh import active_mesh, make_mesh

LIVES = (1, 33, 64, 65, 96)
# (H, P, G, N): one head a row of the ``x`` ring and two (``heads_a_row`` at toy
# widths is asked of the plane's shape, as the launch asks), one group and two
GEOS = {"f1-g2": (4, 16, 2, 24, 1), "f1-g1": (4, 16, 1, 24, 1),
        "f2-g1": (8, 8, 1, 12, 2), "f2-g2": (8, 8, 2, 12, 2)}


# one compiled interpreter a geometry: every case below calls the launch with
# five slots of a geometry's planes
_walk = jax.jit(functools.partial(ssm.recent_walk, interpret=True))
SLOTS = 5


def _inputs(geo, lives, bases, seed=0, layers=2):
    h, p, g, n, f = GEOS[geo]
    rng = np.random.RandomState(seed)
    rk = jnp.asarray(rng.standard_normal((layers, SLOTS, g, RING, n)), jnp.bfloat16)
    rv = jnp.asarray(rng.standard_normal((layers, SLOTS, h // f, RING, f * p)),
                     jnp.bfloat16)
    rg = jnp.asarray(rng.uniform(0.01, 0.1, (layers, SLOTS, 1, RING, h)),
                     jnp.float32)
    base = jnp.asarray(bases, jnp.int32)
    pos = base + jnp.asarray(lives, jnp.int32) - 1
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((SLOTS, g, 1, n)), jnp.bfloat16)
    return c, (rk, rv, rg), a, jnp.int32(layers - 1), pos, base


def _both(c, planes, a, li, pos, base):
    return (_walk(c, *planes, a, li, pos, base),
            ssm._recent(c.astype(jnp.float32), *planes, a, li, pos, base))


def _close(got, want, tol=1e-6):
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(x)))
        assert float(jnp.max(jnp.abs(x - y))) <= tol * max(
            1.0, float(jnp.max(jnp.abs(y))))


@pytest.mark.parametrize("live", LIVES)
@pytest.mark.parametrize("geo", sorted(GEOS))
def test_the_launch_reads_what_the_xla_form_reads(geo, live):
    """Every live count that changes the chunks the launch copies (one
    position, a chunk and a row, the first half full, one row into the second
    half, the most a ring holds) from watermarks in both halves of the ring
    and from position 0, beside slots of other counts."""
    others = [n for n in LIVES if n != live][:2]
    _close(*_both(*_inputs(geo, [live, live, live] + others,
                           [0, FOLD, 2 * FOLD, 3 * FOLD, 0], seed=live)))


@pytest.mark.parametrize("geo", sorted(GEOS))
def test_a_row_whose_dt_is_0_neither_decays_nor_feeds(geo):
    """Left padding and rows past ``n_real`` are written with ``dt`` 0
    (``live_dt``): the launch gives them weight 0 and no decay, as the XLA
    form does, wherever they lie in the live run."""
    c, (rk, rv, rg), *rest = _inputs(geo, [96, 40, 17, 64, 1],
                                     [FOLD, 0, 0, 2 * FOLD, FOLD], seed=7)
    rg = rg * (jnp.arange(RING) % 5 != 2)[:, None]
    assert int(jnp.sum(rg == 0)) > 0
    _close(*_both(c, (rk, rv, rg), *rest))


@pytest.mark.parametrize("geo", sorted(GEOS))
def test_what_lies_past_the_live_run_is_selected_away(geo):
    """NaN in every ring position outside ``[base, pos]``, of all three
    planes: the launch's ``y`` and ``gq`` are finite and what they are without
    it, bit for bit.  (A buffer's tail past the slot's last chunk is what
    interpret mode leaves a scratch buffer as: NaN too.)  The XLA form
    multiplies such a position's ``C . B`` by a weight of 0 and is not asked."""
    c, planes, a, li, pos, base = _inputs(
        geo, LIVES, [0, FOLD, 2 * FOLD, FOLD, 3 * FOLD], seed=3)
    clean, want = _both(c, planes, a, li, pos, base)
    gone = ((jnp.arange(RING)[None, :] - base[:, None]) % RING
            > (pos - base)[:, None])[None, :, None, :, None]
    got = _walk(c, *(jnp.where(gone, jnp.nan, x) for x in planes), a, li, pos, base)
    _close(got, want)
    for x, y in zip(got, clean):
        assert jnp.array_equal(x, y)


def test_the_whole_read_is_the_xla_forms_with_the_launch_in_it(monkeypatch):
    """``ssm.read`` with the launch for its part ``recent`` (the rule made to
    take it, the launch in interpret mode) against ``read`` as the CPU has it:
    the state's product, ``since`` from the launch's ``gq`` and 0 for a slot
    whose watermark is 0."""
    c, planes, a, li, pos, base = _inputs(
        "f2-g2", [5, 70, 33, 96, 64], [0, FOLD, 2 * FOLD, 0, 3 * FOLD], seed=5)
    h, p, _, n, _ = GEOS["f2-g2"]
    rs = jnp.asarray(np.random.RandomState(5).standard_normal(
        (2, SLOTS, h, n, p)), jnp.float32)
    args = (c, rs, *planes, a, li, pos, base)
    want = ssm.read(*args)
    monkeypatch.setattr(ssm, "_read_form", lambda *_: "recent-walk")
    monkeypatch.setattr(ssm, "recent_walk", _walk)
    got = ssm.read(*args)
    _close((got,), (want,))
    # a slot whose watermark is 0 reads no state: its y is the rings' alone
    rings = _walk(c, *planes, a, li, pos, base)[0]
    assert jnp.array_equal(got[0], rings[0]) and jnp.array_equal(got[3], rings[3])
    assert not jnp.array_equal(got[1], rings[1])


WIDE = (18, 32, 32, RING, 128)      # Falcon-H1's ``x`` ring


def test_the_rule_reads_static_facts(monkeypatch):
    """The launch for a call of one token a row of two rows or more on one TPU
    device whose ``x`` ring's rows fill whole lanes; the XLA form for the CPU,
    a mesh, a chunk's rows, one row and a toy's narrow rows."""
    form = lambda shape, rows=None, t=1: ssm._read_form(  # noqa: E731
        shape, shape[1] if rows is None else rows, t)
    assert form(WIDE) == "xla"                                   # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert form(WIDE) == "recent-walk"
    assert form(WIDE, t=16) == "xla" and form(WIDE, t=2) == "xla"
    assert form(WIDE, rows=1) == "xla" and ssm.WALK_MIN_ROWS == 2
    assert form(WIDE, rows=2) == "recent-walk"
    with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
        assert form(WIDE) == "xla"
    with active_mesh(make_mesh(tp=1, devices=jax.devices()[:1])):
        assert form(WIDE) == "recent-walk"
    # Granite's heads of 64 two to a row; heads of 64 one to a row; the toys
    assert form((18, 16, 64, RING, 128)) == "recent-walk"
    assert form((18, 16, 128, RING, 64)) == "xla"
    assert form((3, 4, 4, RING, 16)) == "xla"
    assert ssm.heads_a_row(128, 64) == 2 and ssm.heads_a_row(32, 128) == 1
    # the chunks of a buffer are whole and hold the most a ring keeps live
    assert ssm.LIVE == FOLD + REWIND == 96 and ssm.WALK == 16
    assert FOLD % ssm.WALK == 0 and ssm.LIVE % ssm.WALK == 0 and ssm.LIVE <= RING


def _wide(name: str):
    """The toys with a mixer whose ``x`` ring fills whole lanes: the widths at
    which the rule has a choice."""
    if name == "falcon-h1":
        return config_mod.tiny_falcon_h1(ssm_heads=2, ssm_head_dim=128)
    return config_mod.tiny_granite_hybrid(ssm_heads=4, ssm_head_dim=64)


def _jaxpr(case: str) -> str:
    name, prog, *shape = case.split("/")
    cfg = _wide(name)
    p = jax.eval_shape(lambda: init_params(cfg, seed=1))
    if prog == "decode":
        return str(jax.make_jaxpr(
            lambda p, t, ca, pos: forward(p, cfg, t, ca, pos))(
            p, jnp.zeros((1, 1), jnp.int32),
            jax.eval_shape(lambda: init_kv_cache(cfg, 1)), jnp.int32(0)))
    b, t = int(shape[0][1:]), int(shape[1][1:])
    paged = cfg.n_full_layers > 0
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 9, 4, slots=b, max_pages=8)
                          if paged else init_kv_cache(cfg, b))
    return str(jax.make_jaxpr(
        lambda p, tk, ca, pr, nv, tb: forward_slots(
            p, cfg, tk, ca, pr, nv, tb if paged else None))(
        p, jnp.zeros((b, t), jnp.int32), pool, jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), jnp.int32), jnp.zeros((b, 8), jnp.int32)))


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# The traced programs of the wide toys at the parent's commit (41bf806), the
# rule reading a TPU but for the last two (``jax.default_backend`` patched;
# nothing runs): a mixed step's 16 tokens a row, a slot program of ONE row and
# the one-stream decode step keep the jaxpr they had whatever the backend, a
# pure-decode step of two rows keeps it on the CPU
PARENT_JAXPRS = {
    "falcon-h1/slots/b2/t16": "9d9a45b052e9aa4c",
    "falcon-h1/slots/b1/t1": "66c24c1ef160907a",
    "falcon-h1/decode": "63313bedfd6b939d",
    "granite/slots/b2/t16": "205ceaf89288a346",
    "granite/slots/b1/t1": "60950a5b6e0d1c07",
    "falcon-h1/slots/b2/t1/cpu": "bb0593c6dc8e046b",
    "granite/slots/b2/t1/cpu": "10aed00f0d0f98d9",
}


@pytest.mark.parametrize("case", sorted(PARENT_JAXPRS))
def test_programs_the_rule_leaves_alone_are_the_parents(case, monkeypatch):
    if not case.endswith("/cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _digest(_jaxpr(case.removesuffix("/cpu"))) == PARENT_JAXPRS[case]


def _read_jaxpr(b: int, t: int) -> str:
    h, p, g, n = 2, 128, 2, 24
    s = jax.ShapeDtypeStruct
    return str(jax.make_jaxpr(lambda *args: ssm.read(*args))(
        s((b, g, t, n), jnp.bfloat16), s((3, b, h, n, p), jnp.float32),
        s((3, b, g, RING, n), jnp.bfloat16), s((3, b, h, RING, p), jnp.bfloat16),
        s((3, b, 1, RING, h), jnp.float32), s((h,), jnp.float32),
        s((), jnp.int32), s((b,), jnp.int32), s((b,), jnp.int32)))


def test_a_mesh_keeps_the_read_it_had(monkeypatch):
    """``ssm.read`` traced with a mesh of two devices active on a TPU is the
    program the CPU traces (the parent's: the case above holds the CPU's)."""
    cpu = _read_jaxpr(2, 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
        assert _read_jaxpr(2, 1) == cpu
    assert _read_jaxpr(2, 16).count("pallas_call") == 0
    assert _read_jaxpr(2, 1) != cpu


@pytest.mark.parametrize("name", ["falcon-h1", "granite"])
def test_a_pure_decode_step_of_several_rows_takes_the_launch(name, monkeypatch):
    """The control of the cases above, and the ledger: at one token a row and
    two rows the rule changes the program on a TPU (one launch a traced mixer
    layer: the layers' scan traces its body once, Granite's period its four
    mixer layers apart, each a call of the ONE traced launch) and the ledger
    has one ``recent-walk`` entry a site beside the ``state-read`` it had; the cumulative sum, the gather and the
    ring-wide products are gone from the program."""
    case = f"{name}/slots/b2/t1"
    obs_dispatch.reset()
    cpu = _jaxpr(case)
    assert "ssm/recent-walk" not in obs_dispatch.dispatches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs_dispatch.reset()
    tpu = _jaxpr(case)
    sites = {k: v for k, v in obs_dispatch.dispatches().items()
             if k.startswith("ssm/")}
    obs_dispatch.reset()
    assert sites["ssm/recent-walk"] == sites["ssm/state-read"] >= 1
    assert set(sites) == {"ssm/recent-walk", "ssm/state-read", "ssm/fold"}
    # the launch is a jit of its own, traced once: a site binds its jaxpr by name
    assert tpu.count("name=recent_walk") == sites["ssm/recent-walk"]
    assert tpu.count("name=ssm_recent_walk") == 1
    assert "ssm_recent_walk" not in cpu and _digest(tpu) != _digest(cpu)
    assert tpu.count("cumsum") < cpu.count("cumsum")
