"""frozen_trunk_ms: device self time per step of the leaf ops under the
program's `frozen_trunk` scope (the frozen blocks: forward, recompute and
the input gradients), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "frozen_trunk")
