"""Device time of the collectives in one decode step, in ms: the self
time of the all-reduce, all-gather, reduce-scatter, collective-permute
and all-to-all ops (their async start and done halves included) in the
fused decode loop, averaged over the chips, over the decode steps the
traced job's ``serve.chunk`` spans carry (``scopes.py``).  Nothing where
the loop ran no collective, as on one chip."""
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["chunks"]:
        return None
    s = scopes.collective_seconds(r, scopes.decode_modules())
    return None if s is None else 1e3 * s / scopes.decode_steps(r)[0]
