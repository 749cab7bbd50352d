"""Model FLOP/s utilization of the traced job, in %: the operations the
job's real prompt and served tokens need (``counts.request_flops``) over
the traced window times the peak bf16 rate of the chips that share the
work (``chips`` times one chip's)."""


def read(ctx):
    window = ctx["trace"]["window_s"]
    if window <= 0 or not ctx["job"]["flops"]:
        return None
    return 100.0 * ctx["job"]["flops"] / (
        window * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
