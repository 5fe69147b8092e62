"""step_mfu: model FLOPs of the traced steps (chipbench.flops) over the
span of those steps on the device and the chips' bf16 peak, in %."""
import json
import os


def peak_flops(kind):
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return float(peaks[kind]["bf16_flops"])


def read(ctx):
    if ctx.trace is None:
        return None
    steps, span_s = ctx.trace.step_runs(ctx.step_module)
    if not steps or span_s <= 0:
        return None
    return 100.0 * ctx.flops_per_step * steps / span_s / (
        ctx.chips * peak_flops(ctx.device_kind))
