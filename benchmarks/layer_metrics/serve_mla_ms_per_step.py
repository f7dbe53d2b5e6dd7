"""kernels: device time of the latent-attention (MLA) block per scheduler step:
everything under the scopes `qkv` (the two down-projections, the latents'
norms, q's up-projection), `rope`, `kv_write` (the latent row into its page),
`attn` (absorb, the walk over live latent rows, or the expanded form) and `wo`.
Follows the window's share of pure-decode steps, as every `serve_*_per_step`
does; `by-scope.json` splits it by scope and program."""

from _scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ["qkv", "rope", "kv_write", "attn", "wo"]) or None
