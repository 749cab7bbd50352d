"""Host time the serving loop spends between device results, per decode
chunk, in ms: the ``serve.wave`` spans of the traced job less the
``serve.wait`` spans in them (encoding and padding, dispatches, swap-ins,
finalizing), over the ``serve.chunk`` spans (``scopes.py``)."""
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or r["sched_host_s"] is None or not r["chunks"]:
        return None
    return 1e3 * r["sched_host_s"] / len(r["chunks"])
