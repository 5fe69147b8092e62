"""host_bound_idle_share: the share (%) of the traced window in which no
XLA module ran on the chip while the Trainer's loop was inside a
`step/get_batch`, `step/dispatch` or `metrics/readback` annotation (device
trace, mean over chips): the idle time that the host loop holds the chip
back by. At most `device_idle_share`."""
from chipbench import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    idle = scopes.host_bound_idle(ctx.trace)
    if idle is None:
        return None
    return 100.0 * idle[0] / idle[1]
