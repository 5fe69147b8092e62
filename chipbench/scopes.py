"""Device time under the program's named scopes, and the device's idle
time while the host loop sits in one of its annotations.

A scope is a `jax.named_scope` of the program (`client_head`,
`frozen_trunk`, `trainable_trunk`, `attention`, `ssm`, `optimizer`). It
stays in the HLO `op_name` of the ops traced inside it, also through
differentiation and rematerialization, where a transform wraps it:
`jit(step)/transpose(jvp(frozen_trunk))/while/body/.../attention/dot_general`.
The program's host spans are profiler annotations of the same name
(`step/dispatch`, with the span's fields after a `#` where the trace
keeps them in the name).
"""
from __future__ import annotations

import re

# the spans of Trainer.run's own loop; host/assemble and host/place run on
# the prefetch thread, beside the device's work
HOST_LOOP = ("step/get_batch", "step/dispatch", "metrics/readback")

_TRANSFORM = re.compile(r"^(?:[A-Za-z_]\w*\()+(.*?)\)+$")


def in_scope(op_name, scope):
    """Whether a `/`-separated component of `op_name` is `scope`, bare or
    wrapped in transforms (`jvp(scope)`, `transpose(jvp(scope))`)."""
    for part in op_name.split("/"):
        m = _TRANSFORM.match(part)
        if part == scope or (m and m.group(1) == scope):
            return True
    return False


def scope_ms(ctx, scope):
    """Device self time per step of the leaf ops under `scope`, in ms;
    None where no op of the traced steps is under it.

    Leaf ops are those of `DeviceTrace.leaf_self_time` (not `while`,
    `call` or `conditional`, whose events hold their bodies' ops), joined
    by name to the compiled HLO. A fusion counts under the scope of its
    root, the instruction whose metadata XLA gives the fusion. Per step:
    over the executions of the step module, as `attention_ms` counts."""
    if ctx.trace is None or not ctx.hlo:
        return None
    steps, _ = ctx.trace.step_runs(ctx.step_module)
    t = sum(secs for name, secs in ctx.trace.leaf_self_time(ctx.hlo).items()
            if in_scope(ctx.hlo[name][2], scope))
    if not steps or t <= 0:
        return None
    return 1000.0 * t / steps


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _overlap(a, b):
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_bound_idle(trace):
    """(seconds, window seconds), each a mean over chips: the time in
    which no XLA module ran on the chip while the host was inside a
    HOST_LOOP annotation, and the traced window. None where the trace
    holds no such annotation."""
    host = _merge((s, e) for n, s, e in trace.host
                  if n.split("#", 1)[0] in HOST_LOOP)
    if not host:
        return None
    idle, window = [], []
    for plane in trace.planes.values():
        mods = _merge((m[1], m[2]) for m in plane["modules"])
        if not mods:
            continue
        gaps = [[a[1], b[0]] for a, b in zip(mods, mods[1:])]
        idle.append(_overlap(gaps, host))
        window.append(mods[-1][1] - mods[0][0])
    return (sum(idle) / len(idle) / 1e9, sum(window) / len(window) / 1e9)
