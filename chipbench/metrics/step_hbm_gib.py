"""step_hbm_gib: device memory of the timed executable by the compiler's
own count (arguments + outputs + temporaries - aliased), in GiB."""


def read(ctx):
    if ctx.compiled is None:
        return None
    m = ctx.compiled.memory_analysis()
    if m is None:
        return None
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return total / 2 ** 30
