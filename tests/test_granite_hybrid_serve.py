"""Granite-4.0-H through the scheduler (``tests/test_granite_hybrid.py`` has the
model's other tests, the toy configuration and the tolerance; this run compiles
eleven programs, so it has a file, and with it a worker, of its own)."""

from __future__ import annotations

import jax
import pytest

from test_granite_hybrid import CFG, TOKS, _logits, _mesh, params, want  # noqa: F401
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_scheduler_serves_the_reference_token_for_token(params, want):
    """Three requests on two slots of a paged engine, out of step, a slot taken
    over by a new request with the last tenant's state and pages left in place:
    every stream is the reference's greedy stream.  What a slot-owned state
    refuses (prefix reuse, preemption) is turned off quietly, as Falcon-H1's."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, seq_len=256, kv_pages=150,
                 kv_page_size=4)
    assert eng.paged and eng.slot_state == "state-space mixers' state"
    sched = SlotScheduler(eng, prefill_chunk=16, prefix_reuse=True, preempt=True)
    assert sched.prefix_cache is None and sched.pool is not None
    try:
        prompts = [[int(t) for t in TOKS[a:a + n]]
                   for a, n in ((0, 150), (10, 37), (3, 70))]
        tickets = [sched.submit(p, max_new=12 + 5 * i)
                   for i, p in enumerate(prompts)]
        for p, t in zip(prompts, tickets):
            out = list(t.tokens())
            greedy = _logits(want["np"], p + out[:-1]).argmax(-1)
            assert out == greedy[len(p) - 1:].tolist()
    finally:
        sched.close()
