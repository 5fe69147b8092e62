"""mm_samples_per_s: samples of every step in the window over the window's
seconds (host clock, first dispatch to the last step's outputs ready)."""


def read(ctx):
    return ctx.counts["samples"] * ctx.steps / ctx.window_s
