"""ssm_scan_ms: device self time per step of the leaf ops whose Python
stack passes through models/mamba.py (the Mamba mixer), in ms."""


def read(ctx):
    if ctx.trace is None or not ctx.hlo:
        return None
    steps, _ = ctx.trace.step_runs(ctx.step_module)
    t = ctx.trace.self_time_in_file(ctx.hlo, "repro/models/mamba.py")
    if not steps or t <= 0:
        return None
    return 1000.0 * t / steps
