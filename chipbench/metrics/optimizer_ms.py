"""optimizer_ms: device self time per step of the leaf ops under the
program's `optimizer` scope (clip, AdamW, the update), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "optimizer")
