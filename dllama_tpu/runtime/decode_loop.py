"""On-device generation loop: K decode steps + sampling in one XLA program.

The reference's decode loop crosses the host boundary every token — logits
to the host sampler, the sampled token back to the cluster
(`generate` dllama.cpp:53-72, `Sampler::sample` tokenizer.cpp:384-407).
That per-token host round trip leaves the device idle between steps.
Here the whole sample→embed→forward chain runs inside
a ``lax.scan``: one dispatch yields a chunk of K tokens and only the int32
token ids cross the boundary.

Sampling parity: greedy (temperature 0) is exact argmax, identical to the
reference.  Temperature/top-k/top-p runs ``sampling.sample_on_device`` —
a branch-for-branch mirror of the host reference's decision rules driven
by one uniform coin per (row, step), so a fixed coin picks the same token
as ``sampling.sample_with_coin`` on the host.  The *coin stream* comes
from the engine's device-resident JAX key (threefry), not the reference's
xorshift; the host Sampler (sampling.py) remains available for bit-exact
parity runs against the reference stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..models.transformer import (KVCache, forward_last, forward_slots,
                                  forward_slots_all)
from ..ops.scopes import scope
from ..sampling import sample_on_device


def _record_sample_dev(rows: int) -> None:
    # trace-time ledger entry (once per compiled call site, like the
    # matmul/attention paths): the sampled stage ran on device, no host
    # round trip
    from ..obs import dispatch as obs_dispatch
    obs_dispatch.record_dispatch("sample", "sample-dev", rows=rows)


def device_sample(logits: jax.Array, key: jax.Array, temperature: float,
                  topp: float, topk: int = 0,
                  mask: jax.Array | None = None) -> jax.Array:
    """Sample token ids (B,) from logits (B, V) on device.

    Mirrors Sampler::sample's modes (tokenizer.cpp:384-407): temperature
    0 → argmax; top-p outside (0,1) → plain multinomial; otherwise
    nucleus sampling — all via :func:`sampling.sample_on_device`, the
    coin-based host mirror.  ``temperature``/``topp``/``topk`` are
    static so each mode compiles to its own minimal program; ``mask`` is
    the optional vocab keep-mask (grammar seam, identity today).
    """
    with scope("sample"):
        if temperature == 0.0:
            if mask is not None:
                logits = jnp.where(jnp.asarray(mask).astype(bool), logits,
                                   -jnp.inf)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        b = logits.shape[0]
        _record_sample_dev(b)
        coins = jax.random.uniform(key, (b,), jnp.float32)
        return sample_on_device(
            logits, coins,
            jnp.full((b,), temperature, jnp.float32),
            jnp.full((b,), topp, jnp.float32),
            jnp.full((b,), topk, jnp.int32), mask=mask)


def decode_chunk(params, cfg: ModelConfig, cache: KVCache, token: jax.Array,
                 pos: jax.Array, key: jax.Array, *, steps: int,
                 temperature: float, topp: float,
                 offsets: jax.Array | None = None):
    """Generate ``steps`` tokens starting from ``token`` (B,) at ``pos``.

    Returns (tokens (steps, B), cache, last_token, new_pos, key).  The
    caller jits this with ``steps``/``temperature``/``topp`` static and the
    cache donated.  Every batch row carries its own token and samples its
    own next token; ``offsets`` (B,) is the ragged-batch left-padding
    vector threaded to the forward pass (per-row RoPE positions and
    attention key floors) so distinct streams decode in lockstep.
    """

    def body(carry, _):
        cache, token, pos, key = carry
        logits, cache = forward_last(params, cfg, token[:, None], cache, pos,
                                     jnp.int32(0), offsets=offsets)
        with scope("sample"):
            key, sub = jax.random.split(key)
            nxt = device_sample(logits, sub, temperature, topp)
            return (cache, nxt, pos + 1, key), nxt

    (cache, last, pos, key), toks = jax.lax.scan(
        body, (cache, token, pos, key), None, length=steps)
    return toks, cache, last, pos, key


def device_sample_rows(logits: jax.Array, key: jax.Array, temps: jax.Array,
                       topps: jax.Array, greedy: bool,
                       topks: jax.Array | None = None,
                       mask: jax.Array | None = None) -> jax.Array:
    """Per-row-parameter sampling (B, V) → (B,) for continuous-batching
    slots: rows belong to *different requests*, so temperature/top-p/
    top-k arrive as (B,) traced arrays rather than static floats — one
    compiled program serves any mix of per-request settings.  Rows with
    temperature 0 take the exact argmax (same op as device_sample's
    greedy mode, so a slot stream is byte-identical to a solo greedy
    run); ``greedy`` is static and compiles an all-greedy batch down to
    the argmax alone (no coin drawn, no key consumed).  Sampled rows run
    :func:`sampling.sample_on_device` — the coin-based mirror of the
    host reference, one uniform coin per row from ``key``.  ``mask`` is
    the optional vocab keep-mask (grammar seam, identity today).
    """
    with scope("sample"):
        if greedy:
            if mask is not None:
                logits = jnp.where(jnp.asarray(mask).astype(bool), logits,
                                   -jnp.inf)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        b = logits.shape[0]
        _record_sample_dev(b)
        coins = jax.random.uniform(key, (b,), jnp.float32)
        if topks is None:
            topks = jnp.zeros((b,), jnp.int32)
        return sample_on_device(logits, coins, temps, topps, topks, mask=mask)


# A slot program's host operands cross as ONE int32 vector, built on the host
# and uploaded by the jitted call itself (PR 55: an upload an operand cost a
# served step 3 ms of Python, an argument an operand still 1.3 ms of
# transfers).  In order: the (B, t) tokens, then B each of positions,
# ``n_valid``, top-k, temperatures and top-p (floats by their bits), one flag
# (the tokens are ``fed``), then the (B, pages) page table.


def pack_slot_operands(tokens, pos_rows, n_valid, temps, topps, topks=None,
                       page_tables=None) -> np.ndarray:
    """The host side: a private, read-only int32 vector whatever the caller's
    dtypes and strides (so one executable a key, and the caller may overwrite
    its buffers at once).  ``tokens`` None: a pipelined step, whose tokens are
    on the device (the flag set, the token column zero).

    Joined as bytes, not assigned into an array: numpy lets go of the GIL
    around every copy of more than 500 elements and every zeroed allocation
    of a KiB, and in a server's process the first such release after a
    fan-out hands the interpreter to the sixteen writer threads it woke,
    ≈0.5 ms of a serial step (chip probe, PERF.md section 6, PR 55);
    ``tobytes`` keeps it."""
    b = len(pos_rows)
    i32 = lambda a: np.asarray(a, np.int32).tobytes()  # noqa: E731
    f32 = lambda a: np.asarray(a, np.float32).tobytes()  # noqa: E731
    toks = bytes(4 * b) if tokens is None else i32(tokens)
    per_row = [i32(pos_rows), i32(n_valid),
               bytes(4 * b) if topks is None else i32(topks),
               f32(temps), f32(topps)]
    table = b"" if page_tables is None else i32(page_tables)
    # the device side finds an operand by where it starts: one of another
    # length would move every later one, where separate arguments could not
    if any(len(v) != 4 * b for v in per_row) \
            or len(toks) % (4 * b) or len(table) % (4 * b):
        raise ValueError("slot operands disagree on the number of rows")
    return np.frombuffer(
        b"".join((toks, *per_row, i32(tokens is None), table)), np.int32)


def unpack_slot_operands(ops: jax.Array, fed: jax.Array, t: int,
                         paged: bool) -> dict:
    """The device side, static slices of ``pack_slot_operands``' vector: the
    operands of :func:`slot_chunk` and :func:`slot_verify_chunk` by their
    parameters' names.  At ``t`` = 1 a step whose flag is set takes its
    tokens from ``fed`` (B,), a prior dispatch's ``last`` that never left
    the device; a host-fed step hands in any (B,) array, so both kinds of
    step run one executable."""
    b = fed.shape[0]
    rows = b * t
    tokens = ops[:rows].reshape(b, t)
    pos_rows, n_valid, topks, temps, topps = (
        ops[rows + j * b:rows + (j + 1) * b] for j in range(5))
    if t == 1:
        tokens = jnp.where(ops[rows + 5 * b] != 0,
                           fed.astype(jnp.int32)[:, None], tokens)
    as_f32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.float32)  # noqa: E731
    return dict(tokens=tokens, pos_rows=pos_rows, n_valid=n_valid,
                temps=as_f32(temps), topps=as_f32(topps), topks=topks,
                page_table=ops[rows + 5 * b + 1:].reshape(b, -1)
                if paged else None)


def slot_chunk(params, cfg: ModelConfig, cache: KVCache, tokens: jax.Array,
               pos_rows: jax.Array, n_valid: jax.Array, key: jax.Array,
               temps: jax.Array, topps: jax.Array,
               topks: jax.Array | None = None, *, steps: int,
               greedy: bool, page_table: jax.Array | None = None,
               vocab_mask: jax.Array | None = None):
    """One continuous-batching dispatch: a mixed prefill/decode forward
    over (B, T) slot rows, then ``steps - 1`` pure decode steps — all one
    XLA program, so slot serving keeps decode_chunk's amortization (only
    (steps, B) int32 ids cross the host boundary).

    Row ``r`` consumes its first ``n_valid[r]`` tokens at positions
    ``pos_rows[r]..``; its first output token is sampled from its last
    valid position, and each subsequent step feeds every row its own
    previous sample.  The scheduler uses ``steps > 1`` (a decode burst)
    only when no slot is mid-prefill; free rows ride along at position 0
    and their samples are discarded host-side.

    Returns (tokens (steps, B), cache, last (B,), key).  ``last`` is the
    final sampled row — the same values as ``tokens[-1]``, surfaced as
    its own output so a pipelined caller can feed it straight into the
    next dispatch as a device array (no device→host→device round trip
    in pure decode).  ``key`` is the advanced device RNG key: sampled
    chunks split one sub-key per step and return the chain tail, so the
    engine can thread it into the next dispatch without a host round
    trip (greedy chunks return it untouched — no coin was drawn).  The
    caller advances per-slot positions host-side (``pos += n_valid``,
    then +1 per extra step).

    ``page_table`` (B, max_pages) switches the cache to a paged pool:
    pages are pre-reserved at admission for the whole request (prompt +
    budget), so the table is constant across the chunk and rides the
    compiled program as one extra int32 operand.
    """
    logits, cache = forward_slots(params, cfg, tokens, cache, pos_rows,
                                  n_valid, page_table=page_table)
    with scope("sample"):
        if not greedy:
            key, sub = jax.random.split(key)
        else:
            sub = key
        first = device_sample_rows(logits, sub, temps, topps, greedy, topks,
                                   vocab_mask)
        pos_rows = pos_rows + n_valid

    def body(carry, _):
        cache, tok, pos_rows, key = carry
        logits, cache = forward_slots(params, cfg, tok[:, None], cache,
                                      pos_rows, jnp.ones_like(pos_rows),
                                      page_table=page_table)
        with scope("sample"):
            if not greedy:
                key, sub = jax.random.split(key)
            else:
                sub = key
            nxt = device_sample_rows(logits, sub, temps, topps, greedy,
                                     topks, vocab_mask)
            return (cache, nxt, pos_rows + 1, key), nxt

    if steps > 1:
        (cache, last, _, key), rest = jax.lax.scan(
            body, (cache, first, pos_rows, key), None, length=steps - 1)
        with scope("sample"):
            toks = jnp.concatenate([first[None], rest], axis=0)
    else:
        toks, last = first[None], first
    return toks, cache, last, key


def slot_verify_chunk(params, cfg: ModelConfig, cache: KVCache,
                      tokens: jax.Array, pos_rows: jax.Array,
                      n_valid: jax.Array, key: jax.Array, temps: jax.Array,
                      topps: jax.Array, topks: jax.Array | None = None,
                      *, greedy: bool, page_table: jax.Array | None = None,
                      vocab_mask: jax.Array | None = None):
    """One ragged slot-verify dispatch (speculative decoding's verify
    side, Leviathan et al. 2023 greedy rule): row ``r`` feeds
    ``[last_token, d_1..d_{n_valid[r]-1}]`` — its previous sample plus
    its proposed draft tokens — and gets back the model's prediction at
    every fed position plus the count of leading drafts that matched.

    Returns ``(preds (B, T), cache, accepted (B,), last (B,), key)``
    (``key`` advanced one split for sampled batches, untouched for
    greedy — same chain contract as :func:`slot_chunk`):

    * ``preds[r, j]`` is the true next token after ``tokens[r, :j+1]``
      (argmax for greedy rows, so every emitted token is byte-identical
      to plain decode); the caller emits ``preds[r, :accepted[r]+1]`` —
      the matched drafts re-derived from the model's own argmax, plus
      the one bonus token the verify forward gives for free.
    * ``accepted[r]`` counts the leading ``preds``-matching drafts,
      clamped to ``n_valid[r] - 1`` so a no-proposal row (``n_valid``
      1) degrades to one plain decode step — one slot speculating never
      perturbs a neighbor that isn't.
    * ``last[r] = preds[r, accepted[r]]`` stays device-resident so a
      pipelined caller could feed it onward like slot_chunk's ``last``.

    Rows with temperature > 0 never carry proposals (the scheduler only
    drafts for greedy rows); their position-0 prediction is drawn with
    their own sampling params so riding a verify burst is equivalent to
    riding a decode burst.  KV rows written for rejected drafts sit
    above the row's accepted ceiling — dead by the same causal-ceiling
    masking that makes slot reuse free.
    """
    logits, cache = forward_slots_all(params, cfg, tokens, cache, pos_rows,
                                      n_valid, page_table=page_table)
    with scope("sample"):
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, T)
        if not greedy:
            key, sub = jax.random.split(key)
            first = device_sample_rows(logits[:, 0], sub, temps, topps,
                                       greedy, topks, vocab_mask)
            preds = preds.at[:, 0].set(first)
        t = tokens.shape[1]
        # leading-match count: draft j (fed at column j+1) is accepted iff
        # it equals the model's prediction at column j and every earlier
        # draft was accepted too — cumprod turns the match mask into
        # leading-ones
        ok = (tokens[:, 1:] == preds[:, :-1]) \
            & (jnp.arange(t - 1)[None, :] < (n_valid - 1)[:, None])
        accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        accepted = accepted.astype(jnp.int32)  # (B,)
        last = jnp.take_along_axis(preds, accepted[:, None], axis=1)[:, 0]
    return preds, cache, accepted, last, key
