"""lm_tokens_per_s: clients x sequences x length of every step in the
window over the window's seconds (host clock)."""


def read(ctx):
    return ctx.counts["tokens"] * ctx.steps / ctx.window_s
