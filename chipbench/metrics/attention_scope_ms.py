"""attention_scope_ms: device self time per step of the leaf ops under
the program's `attention` scope, in ms: forward, rematerialized forward
and backward (`attention_ms` reads the forward alone)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attention")
