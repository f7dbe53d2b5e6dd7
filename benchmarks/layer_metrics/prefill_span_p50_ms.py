"""engine: median duration of the `engine.prefill` spans in the traced window
(enqueue, device, logits fetch of one prompt), on the profiler's clock."""

from _scopes import table


def read(ctx):
    tab = table(ctx)
    spans = (tab or {}).get("spans", {}).get("engine.prefill")
    return spans[len(spans) // 2] / 1e6 if spans else None
