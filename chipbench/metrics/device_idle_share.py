"""device_idle_share: the share (%) of the traced window in which no XLA
module ran on the chip (device trace, mean over chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
