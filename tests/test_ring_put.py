"""A pure-decode step's ring writes (``ops/window.py``, PR 66): one token a row
goes into a stacked plane in ONE launch (``ring_put``: the rows' aligned windows,
all in flight together) or ONE fused update of the layer's slab (``_put_slab``)
where every call used to issue a window a row (``_write_row``).  The launch (in
interpret mode) and the slab are held to the windows bit for bit; the rule
(``_put_form``) to its static facts; the programs the rule leaves alone (more
than one token a row, one row, the CPU) to the jaxprs they had before it."""

from __future__ import annotations

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
from dllama_tpu.ops import conv, ssm, window
from dllama_tpu.parallel.mesh import active_mesh, make_mesh


def _windows(ring, new, layer, pos):
    li = jnp.int32(layer)
    for row in range(new.shape[0]):
        ring = window._write_row(ring, new[row], li, row, pos[row], ring.shape[3])
    return ring


def _slots(b: int, r: int, seed: int) -> np.ndarray:
    """Slots {0, 1, 15, 16, R - 1} mixed across the rows, each some turns of
    the ring deep, the rest drawn."""
    edge = np.array([0, 1, 15, 16, r - 1])
    rng = np.random.RandomState(seed)
    slot = np.where(np.arange(b) < 2 * len(edge), edge[np.arange(b) % len(edge)],
                    rng.randint(0, r, b))
    return (rng.permutation(slot) + r * rng.randint(0, 9, b)).astype(np.int32)


def _put(form: str):
    return {"put-kernel": lambda *a: window.ring_put(*a, interpret=True),
            "put-slab": window._put_slab}[form]


@pytest.mark.parametrize("form", ["put-kernel", "put-slab"])
@pytest.mark.parametrize("r", [64, 128])
@pytest.mark.parametrize("b", [2, 8, 16, 32])
@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_one_put_writes_what_the_windows_write(dt, b, r, form):
    """Every row's token lands in its slot and nothing else of the plane moves,
    at another layer than the first, from float32 activations rounded as
    ``_write_row`` rounds them."""
    rng = np.random.RandomState(b + r)
    ring = jnp.asarray(rng.standard_normal((3, b, 2, r, 128)), dt)
    new = jnp.asarray(rng.standard_normal((b, 2, 1, 128)), jnp.float32)
    pos = jnp.asarray(_slots(b, r, b))
    got = _put(form)(ring, new, jnp.int32(1), pos)
    assert got.dtype == ring.dtype
    assert jnp.array_equal(got, _windows(ring, new, 1, pos))
    assert jnp.array_equal(got[0], ring[0]) and jnp.array_equal(got[2], ring[2])


# Granite's ``x`` ring, two heads of 64 to a row of 128 (``ssm.heads_a_row``), its
# ``dt`` ring with rows masked to 0, Falcon-H1's ``dt`` ring of 32 heads (the
# slab's alone: Mosaic copies no part of a 128-lane row) and a convolution's
# ring of one wide row
@pytest.mark.parametrize("name,form,shape,dt", [
    ("granite.rv", "put-kernel", (2, 16, 8, 128, 128), jnp.bfloat16),
    ("granite.rv", "put-slab", (2, 16, 8, 128, 128), jnp.bfloat16),
    ("granite.rg", "put-kernel", (2, 16, 1, 128, 128), jnp.float32),
    ("falcon.rg", "put-slab", (2, 32, 1, 128, 32), jnp.float32),
    ("falcon.cz", "put-kernel", (2, 32, 1, 64, 640), jnp.bfloat16),
])
def test_the_mixers_planes_are_put_as_the_windows_put_them(name, form, shape, dt):
    _, b, h, r, dh = shape
    rng = np.random.RandomState(3)
    ring = jnp.asarray(rng.standard_normal(shape), dt)
    pos = jnp.asarray(_slots(b, r, 1))
    if name == "granite.rv":
        x = jnp.asarray(rng.standard_normal((b, 2 * h, 1, dh // 2)), jnp.bfloat16)
        new = ssm._paired(x, h)
    elif name.endswith(".rg"):
        live = ssm.live_dt(jnp.asarray(rng.uniform(0.01, 0.1, (b, 1, dh)),
                                       jnp.float32), pos, n_real=jnp.arange(b) % 2)
        assert int(jnp.sum(live == 0)) == (b // 2) * dh
        new = live[:, None]
    else:
        new = jnp.asarray(rng.standard_normal((b, h, 1, dh)), jnp.bfloat16)
    got = _put(form)(ring, new, jnp.int32(1), pos)
    assert jnp.array_equal(got, _windows(ring, new, 1, pos))


def test_a_launch_over_more_windows_than_fit_takes_the_rows_in_turns(monkeypatch):
    """``PUT_VMEM`` bounds the windows a grid step holds: 16 rows in steps of
    4, the same plane."""
    rng = np.random.RandomState(0)
    ring = jnp.asarray(rng.standard_normal((2, 16, 2, 64, 128)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((16, 2, 1, 128)), jnp.bfloat16)
    pos = jnp.asarray(_slots(16, 64, 2))
    one = 2 * 16 * 128 * 2
    assert window._put_rows(ring.shape, ring.dtype, 16) == 16
    monkeypatch.setattr(window, "PUT_VMEM", 5 * one)
    assert window._put_rows(ring.shape, ring.dtype, 16) == 4
    assert jnp.array_equal(window.ring_put(ring, new, jnp.int32(0), pos,
                                           interpret=True),
                           _windows(ring, new, 0, pos))
    monkeypatch.setattr(window, "PUT_VMEM", one - 1)
    assert window._put_rows(ring.shape, ring.dtype, 16) == 0


BIG = (18, 32, 32, 128, 128)      # Falcon-H1's ``x`` ring: 0.6 GB


def test_the_rule_reads_static_facts(monkeypatch):
    """The CPU, a mesh, more than one token a row and one row keep the windows;
    on one TPU device the launch takes every ring of whole aligned windows whose
    rows fill whole lanes, whatever the plane's size (its VMEM scope keeps XLA
    from moving a plane that would fit there), the slab a narrower ring whose
    rows are light, the windows a narrow heavy one."""
    form = lambda shape, dt, t=1: window._put_form(shape, dt, shape[1], t)  # noqa: E731
    assert form(BIG, jnp.bfloat16) == "windows"                  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert form(BIG, jnp.bfloat16) == "put-kernel"
    assert form(BIG, jnp.bfloat16, t=16) == "windows"
    assert form(BIG, jnp.bfloat16, t=2) == "windows"
    assert form((18, 1, 32, 128, 128), jnp.bfloat16) == "windows"      # one row
    assert window.PUT_MIN_ROWS == 2
    assert form((18, 2, 32, 128, 128), jnp.bfloat16) == "put-kernel"
    with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
        assert form(BIG, jnp.bfloat16) == "windows"
    with active_mesh(make_mesh(tp=1, devices=jax.devices()[:1])):
        assert form(BIG, jnp.bfloat16) == "put-kernel"
    # Falcon-H1's cz, rk and rg, Granite's rg, Brumby's keys and gates, LFM2's
    # ring, a toy's; heads of 64 kept one a row, heavy and light
    for shape, dt, want in (
            ((18, 32, 1, 64, 5120), jnp.bfloat16, "put-kernel"),
            ((18, 32, 2, 128, 256), jnp.bfloat16, "put-kernel"),
            ((18, 32, 1, 128, 32), jnp.float32, "put-slab"),
            ((18, 16, 1, 128, 128), jnp.float32, "put-kernel"),
            ((40, 8, 8, 128, 128), jnp.bfloat16, "put-kernel"),
            ((40, 8, 1, 128, 8), jnp.float32, "put-slab"),
            ((30, 16, 1, 64, 2048), jnp.bfloat16, "put-kernel"),
            ((2, 4, 2, 64, 16), jnp.float32, "put-slab"),
            ((40, 32, 32, 128, 64), jnp.bfloat16, "windows"),
            ((40, 32, 4, 128, 64), jnp.bfloat16, "put-slab")):
        assert form(shape, dt) == want, shape
    assert window.PUT_SLAB_ROW == 128 * 1024
    # an aligned window of a ring: 16 positions of bfloat16, 8 of float32
    assert window._put_window(jnp.bfloat16) == 16
    assert window._put_window(jnp.float32) == 8
    assert form((18, 32, 32, 120, 128), jnp.bfloat16) == "windows"
    assert form((18, 32, 32, 120, 128), jnp.float32) == "put-kernel"
    # the scope the launch asks for leaves a plane no room beside it in VMEM
    assert window.PUT_VMEM < window.PUT_SCOPE < 128 << 20


def test_the_ledger_has_one_entry_a_site(monkeypatch):
    """A mixer layer's four planes are four sites (``{codec="ring"}``), a window
    layer's keys and values one; the path is the rule's."""
    b, layer = 4, jnp.int32(0)
    planes = ssm.init_planes(config_mod.tiny_falcon_h1(), b, jnp.float32)
    h, g = planes["rs"].shape[2], planes["rk"].shape[2]
    n, p, ch = planes["rk"].shape[4], planes["rv"].shape[4], planes["cz"].shape[4]

    def writes(t):
        pos = jnp.arange(b, dtype=jnp.int32)
        ssm.write(planes["rk"], planes["rv"], planes["rg"], jnp.zeros((b, g, t, n)),
                  jnp.zeros((b, h, t, p)), jnp.zeros((b, t, h)), layer, pos)
        conv.state_write(planes["cz"], jnp.zeros((b, t, ch)), layer, pos, 4)

    def ring():
        return {k: v for k, v in obs_dispatch.dispatches().items()
                if k.startswith("ring/")}

    obs_dispatch.reset()
    jax.make_jaxpr(lambda: writes(1))()
    assert ring() == {"ring/windows": 4}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs_dispatch.reset()
    jax.make_jaxpr(lambda: writes(16))()
    assert ring() == {"ring/windows": 4}
    obs_dispatch.reset()
    jax.make_jaxpr(lambda: writes(1))()
    assert ring() == {"ring/put-slab": 4}       # a toy's planes fit VMEM
    obs_dispatch.reset()
    kv = jnp.zeros((2, b, 2, 64, 16))
    jax.make_jaxpr(lambda: window.ring_write(
        kv, kv, kv[0, :, :, :1], kv[0, :, :, :1], layer, jnp.arange(b)))()
    assert ring() == {"ring/put-slab": 1}
    obs_dispatch.reset()
    big = jax.ShapeDtypeStruct(BIG, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda ring_: window.ring_write_plane(
        ring_, jnp.zeros((32, 32, 1, 128), jnp.bfloat16), layer,
        jnp.arange(32)))(big))
    assert ring() == {"ring/put-kernel": 1}
    assert text.count("pallas_call") == 1 and "dynamic_update_slice" not in text
    obs_dispatch.reset()


# The traced programs of the toy configurations with the rule reading a TPU
# (``jax.default_backend`` patched; nothing runs), at the parent's commit
# (7838b87): a mixed step's 16 tokens a row, a slot program of ONE row and the
# one-stream decode step keep the jaxpr they had, whatever the backend
PARENT_JAXPRS = {
    "falcon-h1/slots/b2/t16": "0a5d24c7cf6ad8b9",
    "falcon-h1/slots/b1/t1": "d764a8306cbf48ed",
    "falcon-h1/decode": "ec2b9b190dcbd525",
    "granite/slots/b2/t16": "75c81a1c9258935f",
    "granite/slots/b1/t1": "3ee4ae07c696b38f",
    "brumby/decode": "9d3898286e827098",
    "lfm2/decode": "ae4d7e73e2d876c9",
    "smallthinker/decode": "df62241b3a7810be",
}
TOYS = {"falcon-h1": config_mod.tiny_falcon_h1, "granite": config_mod.tiny_granite_hybrid,
        "brumby": config_mod.tiny_brumby, "lfm2": config_mod.tiny_lfm2_moe,
        "smallthinker": config_mod.tiny_smallthinker}


def _jaxpr(case: str) -> str:
    name, prog, *shape = case.split("/")
    cfg = TOYS[name]()
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


@pytest.mark.parametrize("case", sorted(PARENT_JAXPRS))
def test_programs_the_rule_leaves_alone_are_the_parents(case, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _digest(_jaxpr(case)) == PARENT_JAXPRS[case]


@pytest.mark.parametrize("name", ["falcon-h1", "granite"])
def test_a_pure_decode_step_of_several_rows_is_another_program(name, monkeypatch):
    """The control of the test above: at one token a row and two rows the rule
    changes the program on a TPU (a toy's planes take the slab: no window is
    left in the mixer's write) and leaves the CPU's alone."""
    case = f"{name}/slots/b2/t1"
    cpu = _jaxpr(case)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs_dispatch.reset()
    tpu = _jaxpr(case)
    sites = {k: v for k, v in obs_dispatch.dispatches().items()
             if k.startswith("ring/")}
    obs_dispatch.reset()
    assert set(sites) == {"ring/put-slab"} and sites["ring/put-slab"] >= 4
    assert _digest(tpu) != _digest(cpu)
    assert tpu.count("dynamic_update_slice") < cpu.count("dynamic_update_slice")
