"""engine: host time of a long prompt's prefill calls per 1000 prompt tokens:
the `engine.prefill_chunk` spans of the traced window (one call of a prompt
longer than one prefill chunk: enqueue and wait; 512 rows through one program
at SmallThinker's widths, then a bucketed tail) over the prompt tokens they
fed.  A 5 s trace holds about 25 calls; the whole prompt's `engine.prefill`
span is seconds long and has mostly begun before the trace did."""

from _decode import chunk_spans


def read(ctx):
    spans = chunk_spans(ctx)
    tokens = sum(k for _, k in spans)
    return sum(s for s, _ in spans) * 1e6 / tokens if tokens else None
