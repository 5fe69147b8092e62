"""ssm_scope_ms: device self time per step of the leaf ops under the
program's `ssm` scope (the Mamba mixer: forward, recompute and backward;
`ssm_scan_ms` reads the forward alone), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ssm")
