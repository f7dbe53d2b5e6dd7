"""kernels: the state-space mixers' share of the device's busy time in the
traced window: the device time `serve_ssm_ms_per_step` sums (the parts `ssm` of
`qkv` and `wo`, `conv` / `state` / `recent` of `attn`, `conv` / `recent` / `fold`
of `kv_write`), over every step of the window, over the table's `busy_s`: the
twin of `serve_moe_share_pct`, which reads one whole scope where the mixer is
parts of four.  With that share it says how much of the step the two mechanisms
are.  `None` where no program carries the part `ssm` (every other arch, and the
parent of the PR that added a mixer)."""

import serve_ssm_ms_per_step
from _scopes import table


def read(ctx):
    ms = serve_ssm_ms_per_step.read(ctx)
    tab = table(ctx)
    if not ms or not tab or not tab["busy_s"]:
        return None
    return 100.0 * ms / 1e3 * tab["steps"] / tab["busy_s"]
