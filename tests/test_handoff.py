"""Per-request KV hand-off tests (runtime/snapshot.py DLREQ01 records,
scheduler export/import — the substrate under the fleet router's
drain-aware rolling restart).

The tentpole contracts, each pinned here on CPU with a tiny model:

* **record integrity** — DLREQ01 dumps/loads round-trips meta + arrays
  exactly; any flipped byte or truncation is an :class:`ArtifactError`,
  never silent corruption; the request-record and snapshot-file magics
  refuse each other's payloads;
* **byte parity** — a greedy request exported mid-decode from one paged
  scheduler and imported into a second (same geometry, same weights)
  resumes decode byte-identically: replayed + resumed tokens equal the
  undisturbed solo run, with no re-prefill;
* **geometry gate** — a record from an incompatible replica (different
  fingerprint, or page payload inconsistent with the record position)
  is rejected with :class:`SnapshotMismatch` before any state is
  touched;
* **queued tickets** — a drain-time export retires never-admitted
  tickets with finish ``handoff`` and no record (the router re-submits
  those from scratch; nothing was streamed, so that is idempotent).
"""

import numpy as np
import pytest

import jax

from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime import snapshot as snapfmt
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.faults import injected
from dllama_tpu.runtime.scheduler import SlotScheduler
from dllama_tpu.runtime.snapshot import SnapshotMismatch

pytestmark = pytest.mark.router

CFG = tiny_config(seq_len=64)
PAGE = 4
P = [5, 9, 2]


def make_paged_engine(batch=2, page=PAGE):
    pages_per_slot = -(-CFG.seq_len // page)
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch,
                  kv_pages=batch * pages_per_slot + 1,
                  kv_page_size=page)


@pytest.fixture(scope="module")
def solo_ref():
    """Greedy solo completion on the contiguous engine — the hand-off
    parity oracle (pages and hand-off are addressing changes, never
    numerics changes)."""
    eng = Engine(CFG, init_params(CFG, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    toks = [t for t, _ in eng.generate_stream(
        P, len(P) + 30, temperature=0.0, chunk=5)]
    return toks[len(P):]


@pytest.fixture(scope="module")
def stack():
    """Two independent paged schedulers with identical geometry and
    weights — exporter and importer of a fleet hand-off."""
    scheds = []
    for _ in range(2):
        eng = make_paged_engine()
        scheds.append(SlotScheduler(eng, prefill_chunk=4,
                                    max_wait_ms=20.0, decode_burst=4))
    yield scheds[0], scheds[1]
    for s in scheds:
        s.close()


# -- DLREQ01 record format -------------------------------------------------

def _mk_record():
    arrays = {
        "pages.k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "pages.v": np.arange(24, 48, dtype=np.float32).reshape(2, 3, 4),
        "rng_key": np.array([7, 11], dtype=np.uint32),
    }
    extra = {"rid": "req-abc", "prompt": [1, 2, 3], "completion": [9, 8],
             "max_new": 16, "temperature": 0.0, "stop": ["END"]}
    blob = snapfmt.dumps_request(fingerprint="fp-1", pos=7, chunk_counter=3,
                                 arrays=arrays, extra=extra)
    return blob, arrays, extra


def test_dlreq01_roundtrip():
    blob, arrays, extra = _mk_record()
    meta, got = snapfmt.loads_request(blob)
    assert meta["fingerprint"] == "fp-1"
    assert meta["pos"] == 7 and meta["chunk_counter"] == 3
    assert meta["extra"] == extra
    assert set(got) == set(arrays)
    for name, arr in arrays.items():
        assert got[name].dtype == arr.dtype
        np.testing.assert_array_equal(got[name], arr)


def test_dlreq01_detects_corruption():
    blob, _, _ = _mk_record()
    # a flipped byte anywhere past the header fails the crc — probe one
    # offset in the json meta and one in the array payload
    for off in (20, len(blob) - 5):
        bad = bytearray(blob)
        bad[off] ^= 0xFF
        with pytest.raises(ArtifactError):
            snapfmt.loads_request(bytes(bad))
    with pytest.raises(ArtifactError):
        snapfmt.loads_request(blob[:len(blob) // 2])  # truncated
    with pytest.raises(ArtifactError):
        snapfmt.loads_request(b"")


def test_magics_are_mutually_exclusive(tmp_path):
    blob, _, _ = _mk_record()
    # a DLSNAP02 snapshot header on a hand-off payload must be refused…
    with pytest.raises(ArtifactError, match="hand-off"):
        snapfmt.loads_request(snapfmt.MAGIC + blob[len(snapfmt.REQ_MAGIC):])
    # …and the snapshot-file loader must refuse a DLREQ01 record on disk
    p = tmp_path / "req.dlsnap"
    p.write_bytes(blob)
    with pytest.raises(ArtifactError):
        snapfmt.load(p)


# -- scheduler export/import ----------------------------------------------

def test_handoff_resume_byte_parity(stack, solo_ref):
    """Export a greedy request mid-decode from scheduler A, import into
    scheduler B, drain it there: replayed + resumed tokens must equal
    the undisturbed solo run — the fleet e2e invariant, in-process."""
    sa, sb = stack
    with injected("engine.device_step=delay:0.05"):
        t = sa.submit(P, 30, temperature=0.0)
        it = t.tokens()
        consumed = [next(it) for _ in range(6)]
        records = sa.handoff_export_all()
    list(it)  # drain the severed stream
    assert t.finish == "handoff"
    assert set(records) == {t.rid}

    meta, _ = snapfmt.loads_request(records[t.rid])
    replayed = [int(x) for x in meta["extra"]["completion"]]
    # the exporter ships everything produced, which is at least what the
    # consumer saw (the dispatch burst may have run ahead of the reader)
    assert replayed[:len(consumed)] == consumed

    t2, extra = sb.import_request(records[t.rid])
    assert t2.rid == t.rid
    assert extra["completion"] == replayed
    resumed = list(t2.tokens())
    assert t2.finish == "length"
    assert replayed + resumed == solo_ref
    # resumption decodes only the remaining budget — no silent re-prefill
    assert len(resumed) == 30 - len(replayed)


def test_import_rejects_incompatible_geometry(stack):
    sa, _ = stack
    blob = snapfmt.dumps_request(
        fingerprint="some-other-fleet", pos=4, chunk_counter=0,
        arrays={"pages.k": np.zeros((2, 1, 2, PAGE, 4), np.float32),
                "pages.v": np.zeros((2, 1, 2, PAGE, 4), np.float32)},
        extra={"rid": "alien", "prompt": [1, 2], "max_new": 4})
    with pytest.raises(SnapshotMismatch, match="geometry"):
        sa.import_request(blob)


def test_record_of_another_page_axis_order_is_refused(monkeypatch, tmp_path):
    """The hand-off and snapshot fingerprints name the axis order inside a
    page (``transformer.PAGE_AXES``), so a record or snapshot written
    head-major — as every file from before PR 27 was — is refused by name,
    even at Hkv == ps where the page's shape is the same either way.  (A
    file from before PR 27 lacks the key altogether, which changes the
    digest just the same.)"""
    from dllama_tpu.runtime import engine as engine_mod

    cfg = tiny_config(n_kv_heads=4, seq_len=32)  # Hkv == ps == 4

    def paged():
        return Engine(cfg, init_params(cfg, seed=4),
                      mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                      batch=2, kv_pages=17, kv_page_size=PAGE)

    now = paged()
    assert now.cache.k.shape[2] == now.cache.k.shape[3] == 4
    monkeypatch.setattr(engine_mod, "PAGE_AXES", "Hkv,ps,Dh")
    old = paged()  # same shapes, the page's axes named the old way round
    old_fp = old.handoff_fingerprint()
    snap = old.snapshot(str(tmp_path / "old.snap"))
    monkeypatch.undo()
    assert now.handoff_fingerprint() != old_fp
    blob = snapfmt.dumps_request(
        fingerprint=old_fp, pos=4, chunk_counter=0,
        arrays={n: np.zeros((cfg.n_layers, 1, 4, 4, cfg.head_size), np.float32)
                for n in ("pages.k", "pages.v")},
        extra={"rid": "old-order", "prompt": [1, 2], "max_new": 4})
    sched = SlotScheduler(now, prefill_chunk=4, max_wait_ms=20.0)
    try:
        with pytest.raises(SnapshotMismatch, match="geometry"):
            sched.import_request(blob)
    finally:
        sched.close()
    with pytest.raises(SnapshotMismatch, match="fingerprint"):
        now.restore(snap)


def test_import_rejects_inconsistent_pages(stack):
    """Right fingerprint, but the page payload disagrees with the record
    position (a torn or doctored export) — refused before any state is
    written."""
    sa, _ = stack
    fp = sa.engine.handoff_fingerprint()
    kvshape = sa.engine.cache.k.shape
    wrong = (kvshape[0], 1) + tuple(kvshape[2:])  # pos=9 needs 3 pages
    blob = snapfmt.dumps_request(
        fingerprint=fp, pos=9, chunk_counter=0,
        arrays={"pages.k": np.zeros(wrong, np.float32),
                "pages.v": np.zeros(wrong, np.float32)},
        extra={"rid": "torn", "prompt": [1, 2], "max_new": 4,
               "fed": 2, "produced": 0})
    with pytest.raises(SnapshotMismatch, match="position"):
        sa.import_request(blob)


def test_export_fails_queued_tickets_without_records(stack):
    """batch=2 scheduler with 3 requests: the two admitted ones export
    records, the queued one retires ``handoff`` with no record."""
    sa, _ = stack
    with injected("engine.device_step=delay:0.05"):
        tickets = [sa.submit([3 + i, 4, 6], 30, temperature=0.0)
                   for i in range(3)]
        its = [t.tokens() for t in tickets]
        next(its[0])  # both slots admitted and decoding
        records = sa.handoff_export_all()
    for it in its:
        list(it)
    assert all(t.finish == "handoff" for t in tickets)
    admitted = {t.rid for t in tickets if t.slot is not None}
    queued = {t.rid for t in tickets} - admitted
    assert len(queued) == 1
    assert set(records) == admitted


def test_tp4_export_tp1_import_byte_parity():
    """Cross-geometry hand-off: a record exported from a tp=4 sharded
    paged scheduler imports into a tp=1 replica and resumes
    byte-identically.  The hand-off fingerprint digests *global* cache
    geometry (page size, heads, head dim), never the mesh shape — a
    pod-slice replica draining into a single-chip spare is exactly the
    rolling-restart path the fleet router exercises."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = tiny_config(hidden_dim=128, n_kv_heads=4, seq_len=64)

    def paged(tp):
        pages_per_slot = -(-cfg.seq_len // PAGE)
        return Engine(cfg, init_params(cfg, seed=4),
                      mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]),
                      batch=2, kv_pages=2 * pages_per_slot + 1,
                      kv_page_size=PAGE)

    solo = Engine(cfg, init_params(cfg, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    toks = [t for t, _ in solo.generate_stream(
        P, len(P) + 30, temperature=0.0, chunk=5)]
    solo_ref = toks[len(P):]

    sa = SlotScheduler(paged(4), prefill_chunk=4, max_wait_ms=20.0,
                       decode_burst=4)
    sb = SlotScheduler(paged(1), prefill_chunk=4, max_wait_ms=20.0,
                       decode_burst=4)
    try:
        assert sa.engine.handoff_fingerprint() == \
            sb.engine.handoff_fingerprint(), \
            "mesh shape must not be part of replica identity"
        with injected("engine.device_step=delay:0.05"):
            t = sa.submit(P, 30, temperature=0.0)
            it = t.tokens()
            for _ in range(6):
                next(it)
            records = sa.handoff_export_all()
        list(it)
        assert t.finish == "handoff"
        meta, _ = snapfmt.loads_request(records[t.rid])
        replayed = [int(x) for x in meta["extra"]["completion"]]
        t2, _ = sb.import_request(records[t.rid])
        resumed = list(t2.tokens())
        assert t2.finish == "length"
        assert replayed + resumed == solo_ref, \
            "tp=4 export → tp=1 import drifted"
    finally:
        sa.close()
        sb.close()
