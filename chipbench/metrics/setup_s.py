"""setup_s: process start to the first timed dispatch (host clock): init,
compile or cache load, the pool, the checked warm-up steps."""


def read(ctx):
    return ctx.setup_s
