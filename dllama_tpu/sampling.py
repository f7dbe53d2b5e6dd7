"""Token sampler: greedy argmax / temperature / top-p (nucleus).

Behavior-compatible with the reference ``Sampler``
(/root/reference/src/tokenizer.cpp:294-415), including the xorshift RNG
(`utils.cpp:53-64`) so that fixed-seed runs are reproducible against the
reference.  The host path is vectorized numpy; ``sample_on_device`` is a
jit-friendly variant that keeps the vocab-size logits on the TPU and
transfers only the chosen token id per step.
"""

from __future__ import annotations

import numpy as np


def xorshift_u32(state: int) -> tuple[int, int]:
    """xorshift RNG step (utils.cpp:53-58). Returns (new_state, value)."""
    state &= 0xFFFFFFFFFFFFFFFF
    state ^= (state >> 12)
    state ^= (state << 25) & 0xFFFFFFFFFFFFFFFF
    state ^= (state >> 27)
    value = ((state * 0x2545F4914F6CDD1D) & 0xFFFFFFFFFFFFFFFF) >> 32
    return state, value


def xorshift_f32(state: int) -> tuple[int, float]:
    """Uniform [0, 1) float (utils.cpp:61-64: top 8 bits discarded / 2^24)."""
    state, value = xorshift_u32(state)
    return state, (value >> 8) / 16777216.0


def softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max()
    e = np.exp(x)
    return e / e.sum()


def sample_mult(probs: np.ndarray, coin: float) -> int:
    """Multinomial via CDF walk (tokenizer.cpp:307-318)."""
    cdf = np.cumsum(probs)
    idx = int(np.searchsorted(cdf, coin, side="right"))
    return min(idx, len(probs) - 1)


def sample_topp(probs: np.ndarray, topp: float, coin: float) -> int:
    """Nucleus sampling (tokenizer.cpp:328-369).

    Keeps candidates with p ≥ (1-topp)/(n-1), sorts descending, truncates at
    cumulative > topp, then samples within the truncated mass.
    """
    n = len(probs)
    cutoff = (1.0 - topp) / (n - 1)
    idx = np.nonzero(probs >= cutoff)[0]
    if len(idx) == 0:
        # degenerate near-uniform distribution: nothing survives the cutoff
        # (reference hits UB here, tokenizer.cpp:344-347); sample plainly
        return sample_mult(probs, coin)
    # stable sort descending by prob; ties keep index order like qsort's
    # comparator returning 0 for equals (implementation-defined but stable
    # here for determinism)
    order = idx[np.argsort(-probs[idx], kind="stable")]
    p = probs[order]
    cum = np.cumsum(p)
    over = np.nonzero(cum > topp)[0]
    last = int(over[0]) if len(over) else len(order) - 1
    r = coin * cum[last]
    pick = int(np.searchsorted(cum[: last + 1], r, side="right"))
    return int(order[min(pick, last)])


def apply_topk(logits: np.ndarray, topk: int) -> np.ndarray:
    """Keep the ``topk`` largest logits (ties at the bar all survive),
    -inf the rest.  0 (or >= n) disables.  Threshold rule (k-th largest
    value, keep ``>=``) matches the device mirror exactly so fixed-coin
    parity holds through ties."""
    n = len(logits)
    if topk <= 0 or topk >= n:
        return logits
    thresh = np.partition(logits, n - topk)[n - topk]
    return np.where(logits < thresh, -np.inf, logits)


def sample_with_coin(logits: np.ndarray, coin: float, *, temperature: float,
                     topp: float, topk: int = 0,
                     mask: np.ndarray | None = None) -> int:
    """One sampling decision from an explicit uniform ``coin`` — the host
    reference the device path (:func:`sample_on_device`) mirrors
    branch-for-branch: vocab mask → top-k filter → temperature →
    (greedy | nucleus | plain multinomial).  ``mask`` is an optional
    boolean keep-vector (the grammar seam — identity today)."""
    logits = np.asarray(logits, dtype=np.float32).reshape(-1)
    if mask is not None:
        logits = np.where(np.asarray(mask, dtype=bool).reshape(-1),
                          logits, -np.inf)
    logits = apply_topk(logits, int(topk))
    if temperature == 0.0:
        return int(np.argmax(logits))
    probs = softmax(logits / temperature)
    if topp <= 0 or topp >= 1:
        return sample_mult(probs, coin)
    return sample_topp(probs, topp, coin)


def sample_on_device(logits, coins, temps, topps, topks, mask=None):
    """Jit-friendly batched mirror of :func:`sample_with_coin`.

    ``logits`` (B, V) stay on device; ``coins``/``temps``/``topps``/
    ``topks`` are (B,) per-row parameters and ``mask`` an optional
    (V,)- or (B, V)-broadcastable boolean keep-mask.  Returns (B,) int32
    token ids.  Every branch reproduces the host reference's decision
    rule on the same f32 probabilities — descending ``top_k`` breaks
    ties by lower index exactly like the host's stable sort, the
    nucleus prefix/cutoff/renormalized-CDF walk follows
    tokenizer.cpp:328-369 — so a fixed coin picks the same token on
    both paths (the distribution-parity test contract)."""
    import jax
    import jax.numpy as jnp

    from .ops.scopes import scope

    with scope("sample"):
        lf = logits.astype(jnp.float32)
        v = lf.shape[-1]
        if mask is not None:
            lf = jnp.where(jnp.asarray(mask).astype(bool), lf, -jnp.inf)

    def row(lr, coin, temp, topp, topk):
        # top-k: k-th largest value as threshold, ties at the bar survive
        svals = jax.lax.top_k(lr, v)[0]
        thresh = svals[jnp.clip(topk - 1, 0, v - 1)]
        lr = jnp.where((topk > 0) & (lr < thresh), -jnp.inf, lr)
        greedy_tok = jnp.argmax(lr).astype(jnp.int32)
        probs = jax.nn.softmax(lr / jnp.where(temp > 0.0, temp, 1.0))
        # plain multinomial: CDF walk = searchsorted(cdf, coin, "right")
        cdf = jnp.cumsum(probs)
        mult_tok = jnp.clip(jnp.sum(cdf <= coin), 0, v - 1).astype(jnp.int32)
        # nucleus: descending probs put every p >= cutoff in a prefix
        sp, si = jax.lax.top_k(probs, v)
        cutoff = (1.0 - topp) / (v - 1)
        cand = sp >= cutoff
        ncand = jnp.sum(cand)
        cum = jnp.cumsum(sp)
        over = (cum > topp) & cand
        last = jnp.where(jnp.any(over), jnp.argmax(over),
                         jnp.maximum(ncand - 1, 0))
        r = coin * cum[last]
        pick = jnp.sum((cum <= r) & (jnp.arange(v) <= last))
        topp_tok = si[jnp.minimum(pick, last)].astype(jnp.int32)
        use_topp = (topp > 0.0) & (topp < 1.0) & (ncand > 0)
        sampled = jnp.where(use_topp, topp_tok, mult_tok)
        return jnp.where(temp == 0.0, greedy_tok, sampled)

    with scope("sample"):
        return jax.vmap(row)(lf, coins, temps, topps,
                             topks.astype(jnp.int32))


class Sampler:
    def __init__(self, vocab_size: int, temperature: float, topp: float,
                 seed: int, topk: int = 0):
        self.vocab_size = vocab_size
        self.temperature = temperature
        self.topp = topp
        self.topk = int(topk)
        self.rng_state = seed & 0xFFFFFFFFFFFFFFFF

    def set_temp(self, temperature: float):
        self.temperature = temperature

    def set_seed(self, seed: int):
        self.rng_state = seed & 0xFFFFFFFFFFFFFFFF

    def sample(self, logits: np.ndarray, mask: np.ndarray | None = None) -> int:
        logits = np.asarray(logits, dtype=np.float32).reshape(-1)[: self.vocab_size]
        if mask is not None:
            mask = np.asarray(mask, dtype=bool).reshape(-1)[: self.vocab_size]
        if self.temperature == 0.0:
            return sample_with_coin(logits, 0.0, temperature=0.0,
                                    topp=self.topp, topk=self.topk, mask=mask)
        self.rng_state, coin = xorshift_f32(self.rng_state)
        return sample_with_coin(logits, coin, temperature=self.temperature,
                                topp=self.topp, topk=self.topk, mask=mask)
