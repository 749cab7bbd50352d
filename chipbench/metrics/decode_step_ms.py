"""Device time of one decode step, in ms: the fused decode loop's device
time in the traced job over the decode steps it ran."""


def read(ctx):
    ex = ctx["trace"]["exec"]["decode_loop"]
    steps = ctx["job"]["decode_steps"]
    if not ex["count"] or not steps:
        return None
    return 1e3 * ex["seconds"] / steps
