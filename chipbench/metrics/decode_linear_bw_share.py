"""Share of its roofline that the decode loop's ``qlinear`` work reaches,
in %: the least time the chip needs for it (``layer_counts.py``: the
larger of the packed INT4 weight bytes over the HBM rate and the
operations over the bf16 rate, for the decoded steps and row-steps the
``serve.chunk`` spans carry) over the device time of the ops under
``qlinear`` (``scopes.py``)."""
import layer_counts
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["chunks"]:
        return None
    s = scopes.scoped_seconds(r, scopes.decode_modules(), "qlinear")
    if not s:
        return None
    steps, row_steps = scopes.decode_steps(r)
    m, peaks = ctx["model"], ctx["peaks"]
    floor_s = max(
        steps * layer_counts.decode_linear_bytes(m)
        / peaks["hbm_bytes_per_s"],
        layer_counts.decode_linear_flops(m, row_steps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / s
