"""client_head_ms: device self time per step of the leaf ops under the
program's `client_head` scope (the clients' tokenizers or adapters, with
their backward), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "client_head")
