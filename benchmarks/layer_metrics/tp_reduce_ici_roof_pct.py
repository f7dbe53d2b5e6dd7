"""collectives: the reduces' share of the inter-chip roof: the bytes a chip
must send a token for its all-reduces, over the chip's inter-chip peak, over
the time `tp_reduce_ms_per_tok` reads.

Bytes (``reduce_bytes_per_token``): a dense block has two column matmuls (wo,
w2), each followed by one all-reduce of a ``hidden_size`` float32 partial (the
ring kernel and the psum both reduce the kernel's f32 accumulator); a
bandwidth-optimal all-reduce over ``tp`` chips sends 2 x (tp - 1) / tp of the
vector from each chip.  The peak is ASSUMED to be the 1600 Gbit/s a chip that
cloud.google.com/tpu/docs/v5e publishes as "interchip interconnect bandwidth"
(harness/peaks.py ``ici_bits_per_s``), all links and both directions
together.  A value in the low single digits says the reduces are bound by
latency (launch, barrier, semaphores), not by the links."""

from _common import traced_tokens
from tp_reduce_ms_per_tok import reduce_s

PARTIAL_BYTES = 4  # float32


def reduce_bytes_per_token(cfg: dict, tp: int) -> float:
    reduces = 2 * cfg["num_hidden_layers"]
    return reduces * 2.0 * (tp - 1) / tp * cfg["hidden_size"] * PARTIAL_BYTES


def read(ctx):
    toks, tr, tp = traced_tokens(ctx), ctx["trace"], ctx["chips"]
    if not toks or not tr["chips"] or tp < 2 or ctx["peaks"] is None:
        return None
    secs = reduce_s(tr)
    if not secs:
        return None
    need = reduce_bytes_per_token(ctx["config"], tp) \
        / (ctx["peaks"]["ici_bits_per_s"] / 8)
    return 100.0 * need * toks / secs
