"""trainable_trunk_ms: device self time per step of the leaf ops under
the program's `trainable_trunk` scope (the trained blocks, with their
weight gradients), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "trainable_trunk")
