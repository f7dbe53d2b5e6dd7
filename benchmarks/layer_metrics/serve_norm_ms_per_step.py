"""kernels: device time under scope `norm` (every RMSNorm: a looped model with
sandwich norms runs four a block application and one more a pass, 772 a step
for Ouro-2.6B, each a small fusion whose cost is its launch) per scheduler
step.  The finer split into the norms that open a branch and the ones that
close it (part `post`) is in `out/by-scope.json`'s op table."""

from _scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ["norm"])
