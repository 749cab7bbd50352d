"""Share of its roofline that the decode loop's ``qlinear`` work reaches,
in %: the least time the chips that share it need for it
(``layer_counts.py``: the larger of the whole model's packed INT4 weight
bytes over the HBM rate and its operations over the bf16 rate, each rate
``chips`` times one chip's, for the decoded steps and row-steps the
``serve.chunk`` spans carry) over the device time of the ops under
``qlinear`` that run no collective (``scopes.py``, averaged over the
chips)."""
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
    m, peaks, chips = ctx["model"], ctx["peaks"], ctx["chips"]
    floor_s = max(
        steps * layer_counts.decode_linear_bytes(m)
        / (chips * peaks["hbm_bytes_per_s"]),
        layer_counts.decode_linear_flops(m, row_steps)
        / (chips * peaks["bf16_flops_per_s"]))
    return 100.0 * floor_s / s
