"""What decides ``correct``.  Three checks, all outside the measured window:

(a) the served path against the float32 reference (the ``last_logits`` of the
    configuration's ``models/<name>.py``) on seeded prompts: the first greedy
    token the server returns for each prompt must be one the reference also
    rates (nearly) highest;
(b) the same greedy request sent alone before and after the window returns the
    same bytes;
(c) the degrade counters (``q40_degrade``, ``attn_degrade``) did not move, and
    nothing compiled inside the window.

The tolerance of (a).  The server exposes no logits on the paged path (listed
for the tracing issue), so tokens are compared, and with random weights the
largest logit changes on rounding; the test is therefore made on the
reference's logits: the served token's reference logit must lie within
``TOL_SIGMA`` standard deviations (of that prompt's logits over the
vocabulary) of the reference's maximum.  The served path computes in bfloat16
with float32 accumulation from 4-bit weights; over 32-60 layers at these widths
that moves a logit by about 0.01-0.03 sigma (measured on the chip, PERF.md),
while the gap between the two highest of 32768-64000 Gaussian logits is about
0.2 sigma, and a token chosen by a wrong computation sits about 4 sigma below
the maximum.  0.08 sigma admits a near-tie that bfloat16 rounding flips and
nothing else: an 8-bit activation path or a dropped layer moves logits by more
than that on most prompts, and every prompt must pass.
"""

from __future__ import annotations

import random

import numpy as np

from . import tokens

TOL_SIGMA = 0.08


def check_prompts(seed: int, n: int, length: int, vocab_size: int) -> list[list[int]]:
    """The prompts' own token ids (the endpoint's overhead is added when the
    reference runs)."""
    rng = random.Random(f"{seed}/correct")
    return [[rng.randrange(3, vocab_size) for _ in range(length)]
            for _ in range(n)]


def served_token(text: str) -> int | None:
    """The id of the first token in a reply's text."""
    if not text:
        return None
    for special, tid in (("<unk>", 0), ("<s>", 1), ("</s>", 2)):
        if text.startswith(special):
            return tid
    return tokens.id_of(text[0])


def compare(ref_logits: np.ndarray, served: list[int | None]) -> dict:
    """Per-prompt margins in sigmas, and the verdict."""
    rows = []
    for logits, tok in zip(ref_logits, served):
        sigma = float(logits.std())
        top2 = np.partition(logits, -2)[-2:]
        gap = None if tok is None else float((logits.max() - logits[tok]) / sigma)
        rows.append({"served": tok, "argmax": int(logits.argmax()),
                     "below_max_sigma": gap,
                     "top2_gap_sigma": float((top2[1] - top2[0]) / sigma)})
    ok = all(r["below_max_sigma"] is not None
             and r["below_max_sigma"] <= TOL_SIGMA for r in rows)
    return {"ok": ok, "tol_sigma": TOL_SIGMA, "prompts": rows,
            "exact": sum(r["served"] == r["argmax"] for r in rows)}
