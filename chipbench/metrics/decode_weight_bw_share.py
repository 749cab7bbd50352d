"""Packed weight bytes one decode step must read (``counts.
decode_weight_bytes``, the whole model) over the HBM bandwidth of the
chips that share them (``chips`` times one chip's) and the measured
decode step, in %.  A lower bound on the step's share of its bandwidth
roofline: the cache's bytes are left out."""


def read(ctx):
    ex = ctx["trace"]["exec"]["decode_loop"]
    steps = ctx["job"]["decode_steps"]
    if not ex["count"] or not steps or ex["seconds"] <= 0:
        return None
    floor_s = ctx["job"]["decode_weight_bytes"] / (
        ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ex["seconds"] / steps)
