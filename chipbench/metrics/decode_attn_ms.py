"""Device time of attention in one decode step, in ms: the ops under the
program's ``attention`` scope (rotary embedding, the cache append, the
cache gather and the scores, softmax and weighted sum; collectives left
out) in the fused decode loop, over the decode steps the traced job's
``serve.chunk`` spans carry (``scopes.py``)."""
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["chunks"]:
        return None
    s = scopes.scoped_seconds(r, scopes.decode_modules(), "attention")
    return None if s is None else 1e3 * s / scopes.decode_steps(r)[0]
