"""host_input_wait_share: the share (%) of the traced window that the
Trainer's loop spent getting its next batch (`step/get_batch` spans of
the window's steps, from the program's run log)."""


def read(ctx):
    if not ctx.spans:
        return None
    waited = sum(s["dur_s"] for s in ctx.spans
                 if s["name"] == "step/get_batch"
                 and s["fields"].get("step", -1) >= ctx.first_window_step)
    return 100.0 * waited / ctx.window_s
